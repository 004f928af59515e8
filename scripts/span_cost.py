"""What one span costs the thread that opens it, file off and on.

    python3 scripts/span_cost.py [spans]     # times the tree the script lies in

Times ``with span(name, step=i): pass`` and a ``phase()`` switch on a fresh
``SpanTracer``, first with no JSONL file (the ring alone, as every run keeps
it) and then with one (``LDT_TRACE_PATH`` as a traced run sets it), and
prints microseconds a call, the best of five passes. The JAX profiler's
``TraceAnnotation`` is entered as in a training process; no device is
touched, so it runs beside a process that holds the chip.
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lance_distributed_training_tpu.obs.spans import SpanTracer  # noqa: E402


def best_us(tracer, n: int, passes: int = 5) -> tuple:
    spans, phases = [], []
    for _ in range(passes):
        t = time.perf_counter()
        for i in range(n):
            with tracer.span("loop.probe", step=i):
                pass
        spans.append((time.perf_counter() - t) / n * 1e6)
        t = time.perf_counter()
        for i in range(n):
            tracer.phase("train.probe", step=i)
        tracer.end_phase()
        phases.append((time.perf_counter() - t) / n * 1e6)
    return min(spans), min(phases)


def main(argv) -> int:
    n = int(argv[0]) if argv else 20000
    off = best_us(SpanTracer(), n)
    with tempfile.TemporaryDirectory() as tmp:
        tracer = SpanTracer(jsonl_path=os.path.join(tmp, "spans.jsonl"))
        on = best_us(tracer, n)
        tracer.close()
    print(f"span_cost n={n}: file off span {off[0]:.2f} us, phase "
          f"{off[1]:.2f} us; file on span {on[0]:.2f} us, phase "
          f"{on[1]:.2f} us")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
