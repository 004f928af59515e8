"""Lightweight span tracing with a Chrome-trace / Perfetto export surface.

A :class:`SpanTracer` records named regions (monotonic-clock start/stop,
parent ids from a per-thread stack) into a bounded ring buffer — the
always-on, ~zero-cost sibling of ``jax.profiler`` traces. Three export
surfaces:

* **Perfetto / chrome://tracing** — :func:`chrome_trace` converts completed
  spans to Chrome trace-event JSON (``ph: "X"`` complete events), written by
  :meth:`SpanTracer.write_chrome_trace` or the ``ldt trace export`` CLI
  (:func:`trace_main`).
* **XPlane passthrough** — every span also enters a
  ``jax.profiler.TraceAnnotation`` when jax is importable, so the same
  regions appear on the host timeline of a ``jax.profiler`` trace whose
  host tracer is on. No-op (and no jax import cost) otherwise.
* **cross-process JSONL** — set ``LDT_TRACE_PATH`` (or pass ``jsonl_path``)
  and completed spans append to a JSONL file one event per line; ``ldt
  trace export --spans that-file`` stitches any number of processes'
  files into one Perfetto-loadable trace. The file is written in batches
  (durability: :meth:`SpanTracer.flush`).

Clocks: span durations come from ``time.monotonic_ns`` (LDT601 forbids
``time.time()`` here); the JSONL/export timestamps are the same monotonic
microseconds. For CROSS-process merge each JSONL additionally carries one
``ldt.clock_sync`` anchor record (``wall_ns`` + ``mono_ns`` captured
together — an epoch *stamp* that intentionally crosses process
boundaries, the lineage clock policy) so ``ldt trace export`` can rebase
every process onto one wall timeline; within a process all math stays
monotonic.

Ring-buffer truncation is observable, not silent: every span dropped off
the full ring increments the ``spans_dropped_total`` counter, and JSONL
files carry cumulative ``ldt.spans_dropped`` markers so ``ldt trace
export`` can report how much the source processes truncated.
"""

from __future__ import annotations

import atexit
import dataclasses
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterator, List, Optional

__all__ = [
    "Span",
    "SpanTracer",
    "default_tracer",
    "span",
    "phase",
    "end_phase",
    "watch_xla_compiles",
    "chrome_trace",
    "trace_main",
]


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed region. Times are ``time.monotonic_ns()`` instants."""

    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int  # 0 = root
    thread_id: int
    pid: int
    attrs: Optional[dict] = None

    @property
    def duration_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def to_event(self) -> dict:
        """Chrome trace-event dict (``ph: "X"`` complete event; ts/dur in
        microseconds — the Perfetto/chrome://tracing contract)."""
        args = {"span_id": self.span_id, "parent_id": self.parent_id}
        if self.attrs:
            args.update(self.attrs)
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.start_ns / 1e3,
            "dur": (self.end_ns - self.start_ns) / 1e3,
            "pid": self.pid,
            "tid": self.thread_id,
            "args": args,
        }


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation`` when jax is importable, else None —
    the tracer must work in decode-only processes without jax installed."""
    global _ANNOTATION_CLS
    if _ANNOTATION_CLS is False:
        return None
    if _ANNOTATION_CLS is None:
        try:
            import jax

            _ANNOTATION_CLS = jax.profiler.TraceAnnotation
        except Exception:  # jax absent/broken: tracer still works
            _ANNOTATION_CLS = False
            return None
    return _ANNOTATION_CLS(name)


_ANNOTATION_CLS = None  # unresolved | False (unavailable) | the class


class SpanTracer:
    """Thread-safe tracer: a ring buffer of completed spans.

    ``capacity`` bounds memory forever (old spans fall off the back — the
    recent-window view an engineer actually opens). ``jsonl_path`` (or the
    ``LDT_TRACE_PATH`` env var) additionally appends every completed span as
    one JSON line, the durable form ``ldt trace export`` consumes.
    """

    # The JSONL is written in batches: lines wait in memory until this many
    # bytes are pending or the oldest pending line is this old.
    FLUSH_BYTES = 1 << 16
    FLUSH_AGE_NS = 250_000_000

    def __init__(self, capacity: int = 4096,
                 jsonl_path: Optional[str] = None):
        self._lock = threading.Lock()  # ring buffer only — never held for IO
        self._io_lock = threading.Lock()  # JSONL handle; a slow flush must
        # not block threads opening spans or appending to the ring
        self._spans: deque = deque(maxlen=max(1, capacity))
        self._local = threading.local()
        self._ids = itertools.count(1)  # GIL-atomic: id allocation is lockless
        self._jsonl = None
        self._jsonl_path = jsonl_path or os.environ.get("LDT_TRACE_PATH")
        self._pending: list = []  # JSONL lines not yet written (_io_lock)
        self._pending_bytes = 0
        self._pending_since = 0  # monotonic ns of the oldest pending line
        self._dropped = 0  # spans pushed off the full ring (see dropped)

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextmanager
    def span(self, name: str, **attrs) -> Iterator[dict]:
        """Record the enclosed block as one span; nests (parent = innermost
        open span on this thread) and mirrors into the jax profiler's host
        timeline when a profiler trace is active.

        Yields the span's attrs dict so attributes only known mid-block
        (``cache_hit``, result sizes) can be added before the span
        closes: ``with span("x") as a: a["hit"] = True``."""
        span_id = next(self._ids)
        parent_id = self._innermost()
        stack = self._stack()
        stack.append(span_id)
        annotation = _annotation(name)
        start = time.monotonic_ns()
        try:
            if annotation is not None:
                with annotation:
                    yield attrs
            else:
                yield attrs
        finally:
            end = time.monotonic_ns()
            stack.pop()
            self._record(Span(
                name=name, start_ns=start, end_ns=end, span_id=span_id,
                parent_id=parent_id, thread_id=threading.get_ident() % 2**31,
                pid=os.getpid(), attrs=attrs or None,
            ))

    def _innermost(self) -> int:
        """Id of the calling thread's innermost open span, else of its
        current phase, else 0 (root)."""
        stack = self._stack()
        if stack:
            return stack[-1]
        current = getattr(self._local, "phase", None)
        return current[2] if current is not None else 0

    def phase(self, name: str, **attrs) -> dict:
        """End the calling thread's current phase and begin ``name`` on the
        same clock reading, so a thread's phases tile its time by
        construction: each phase's ``end_ns`` IS the next one's
        ``start_ns``. A phase is an ordinary :class:`Span` (root of its
        thread; spans opened inside it get it as parent), recorded when the
        next ``phase()`` or :meth:`end_phase` closes it. Returns the attrs
        dict, as :meth:`span` yields it."""
        self._switch_phase(name, attrs)
        return attrs

    def end_phase(self) -> None:
        """End the calling thread's current phase and begin none."""
        self._switch_phase(None, None)

    def _switch_phase(self, name: Optional[str], attrs) -> None:
        now = time.monotonic_ns()
        previous = getattr(self._local, "phase", None)
        self._local.phase = None
        if previous is not None:
            p_name, p_start, p_id, p_attrs, p_annotation = previous
            if p_annotation is not None:
                p_annotation.__exit__(None, None, None)
            self._record(Span(
                name=p_name, start_ns=p_start, end_ns=now, span_id=p_id,
                parent_id=0, thread_id=threading.get_ident() % 2**31,
                pid=os.getpid(), attrs=p_attrs or None,
            ))
        if name is not None:
            annotation = _annotation(name)
            if annotation is not None:
                annotation.__enter__()
            self._local.phase = (name, now, next(self._ids), attrs,
                                 annotation)

    def record_complete(self, name: str, start_ns: int, end_ns: int,
                        **attrs) -> None:
        """Record a span whose duration is only reported after the fact
        (a compile, by jax.monitoring): the times are kept as given, the
        parent is whatever the calling thread is inside right now."""
        self._record(Span(
            name=name, start_ns=int(start_ns), end_ns=int(end_ns),
            span_id=next(self._ids), parent_id=self._innermost(),
            thread_id=threading.get_ident() % 2**31, pid=os.getpid(),
            attrs=attrs or None,
        ))

    def _record(self, span: Span) -> None:
        dropped = 0
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                # The ring evicts the oldest span to admit this one —
                # count it so a truncated in-process trace is diagnosable
                # (the old behavior dropped silently).
                self._dropped += 1
                dropped = self._dropped
            self._spans.append(span)
        if dropped:
            # Lazy import: registry never imports spans, so no cycle.
            from .registry import default_registry

            default_registry().counter("spans_dropped_total").inc()
        if self._jsonl_path is None:
            return
        # Serialize outside the ring lock, and write in batches: a stalled
        # disk slows the thread whose record fills the batch, not every
        # thread opening a span, and a span costs no system call of its own
        # (durability: see flush).
        line = json.dumps(span.to_event()) + "\n"
        if dropped and (dropped & (dropped - 1)) == 0:
            # Cumulative drop marker at power-of-two counts: the ring in
            # steady-state overflow drops one span per record, so a
            # per-drop marker would double the file; doubling cadence
            # keeps the count accurate within 2x at O(log n) lines.
            line += json.dumps({
                "name": "ldt.spans_dropped", "ph": "C",
                "pid": span.pid, "tid": 0,
                "ts": span.end_ns / 1e3,
                "args": {"dropped": dropped},
            }) + "\n"
        now = time.monotonic_ns()
        with self._io_lock:
            if self._jsonl_path is None:
                return
            if not self._pending:
                self._pending_since = now
            self._pending.append(line)
            self._pending_bytes += len(line)
            # the first line opens the file at once, so a live reader finds
            # it and its clock anchor as soon as the process records
            if self._jsonl is None \
                    or self._pending_bytes >= self.FLUSH_BYTES \
                    or now - self._pending_since >= self.FLUSH_AGE_NS:
                self._write_pending()

    def _write_pending(self) -> None:
        """Write and flush what is pending; ``_io_lock`` is held."""
        if self._jsonl_path is None or not self._pending:
            return
        if self._jsonl is None:
            try:
                self._jsonl = open(self._jsonl_path, "a")
            except OSError:
                self._jsonl_path = None  # never retry a bad path
                self._pending.clear()
                return
            # Whatever is pending when the interpreter exits is written
            # then; close() takes the hook back.
            atexit.register(self.close)
            # One wall/monotonic anchor pair per (process, open):
            # what lets `ldt trace export` place this process's
            # monotonic timestamps on the shared wall timeline. An
            # epoch stamp crossing processes — the LDT601-sanctioned
            # use (see obs/lineage.py's clock policy).
            self._jsonl.write(json.dumps({
                "name": "ldt.clock_sync", "ph": "M",
                "pid": os.getpid(), "tid": 0, "ts": 0,
                "args": {
                    "wall_ns": time.time_ns(),
                    "mono_ns": time.monotonic_ns(),
                },
            }) + "\n")
        self._jsonl.write("".join(self._pending))
        self._jsonl.flush()
        self._pending.clear()
        self._pending_bytes = 0

    def flush(self) -> None:
        """Write every completed span to the JSONL file now. Durability
        without it: a line reaches the file when ``FLUSH_BYTES`` are
        pending, when a later span completes and finds the oldest pending
        line ``FLUSH_AGE_NS`` (a quarter of a second) old, at
        :meth:`close`, and when the interpreter exits. So a reader of a
        live file sees a busy process at most a quarter of a second behind
        and a quiet one up to its last flush; a process killed outright
        (SIGKILL, ``os._exit``) loses what was pending, at most the spans of
        its last quarter second of activity."""
        with self._io_lock:
            self._write_pending()

    # -- reading / export --------------------------------------------------

    def spans(self) -> List[Span]:
        """Completed spans, oldest first (bounded by ``capacity``)."""
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        """Spans pushed off the full ring since construction."""
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def chrome_trace(self) -> dict:
        out = chrome_trace([s.to_event() for s in self.spans()])
        dropped = self.dropped
        if dropped:
            out["otherData"]["spans_dropped"] = dropped
        return out

    def write_chrome_trace(self, path: str) -> str:
        """Dump the ring buffer as a Perfetto-loadable JSON file."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")
        return path

    def close(self) -> None:
        """Terminal: spans completing after close (e.g. on a daemon thread
        racing shutdown) still enter the ring buffer but no longer reopen
        the JSONL file."""
        with self._io_lock:
            self._write_pending()
            self._jsonl_path = None
            self._pending.clear()
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None
                atexit.unregister(self.close)


def chrome_trace(events: List[dict]) -> dict:
    """Wrap trace events in the Chrome trace-event JSON envelope."""
    return {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
        "otherData": {"exporter": "ldt trace export"},
    }


_DEFAULT: Optional[SpanTracer] = None
_DEFAULT_LOCK = threading.Lock()


def default_tracer() -> SpanTracer:
    """The process-wide tracer every instrumented layer records into.
    Created lazily so ``LDT_TRACE_PATH`` set by the entry point (CLI, test)
    is read at first use, not at import."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = SpanTracer()
        return _DEFAULT


def span(name: str, **attrs):
    """Record a region on the process-wide tracer — the one-liner the
    instrumented modules use: ``with span("svc.decode", step=n): …``."""
    return default_tracer().span(name, **attrs)


def phase(name: str, **attrs) -> dict:
    """Begin phase ``name`` on the calling thread, ending its current one
    on the same clock reading (:meth:`SpanTracer.phase`)."""
    return default_tracer().phase(name, **attrs)


def end_phase() -> None:
    """End the calling thread's current phase (:meth:`SpanTracer.end_phase`)."""
    default_tracer().end_phase()


# -- tracing, lowering and compiling, seen by the program itself --------------

# JAX's duration event -> (span name, counter of its seconds)
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("jax.trace", "jax_trace_seconds_total"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax.lower", "jax_lower_seconds_total"),
    "/jax/core/compile/backend_compile_duration":
        ("xla.compile", "xla_compile_seconds_total"),
}
# What JAX records, on the compiling thread and inside the compile, about the
# persistent cache (jax/_src/compiler.py, compile_or_get_cached). The first
# fires for every program the cache is asked for, the second when it had it.
# `/jax/compilation_cache/cache_misses` is no test for a miss: it fires only
# when an entry is written, which a compile under
# jax_persistent_cache_min_compile_time_secs never is.
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# A trace or a lowering shorter than this leaves no span (the counters take
# it): a model's step traces thousands of `jnp` functions inside its own
# trace, microseconds each, which the step's span covers; together they would
# push a whole start-up out of the ring.
_MIN_TRACE_SPAN_S = 1e-3
_COMPILE_LISTENER_ON = False  # jax.monitoring has no unregister: once a process


def watch_xla_compiles() -> None:
    """Register (once per process) the ``jax.monitoring`` listeners that turn
    what JAX does to a program before it can run into spans, back-dated from
    JAX's duration events on the thread that did the work (parent = the
    phase or span that thread is inside, so the step number comes with it):

    * ``jax.trace`` and ``jax.lower`` (attr ``fun_name``): a function traced
      to a jaxpr, a jaxpr lowered to an MLIR module. A ``jit`` met inside
      another's trace is traced inside it and ends first, so traces nest by
      interval on their thread: a reader takes the union of a kind's
      intervals, thread by thread, never the sum of durations. One under a
      millisecond leaves no span.
    * ``xla.compile`` (attrs ``fun_name``, ``cache``, on a hit
      ``retrieval_s``): the backend compile or the load from the persistent
      compile cache. ``cache`` is ``hit`` or ``miss`` where JAX asked a
      cache directory for the program, ``off`` where it did not (no
      directory, or a program JAX does not cache).

    Counters: ``xla_compiles_total`` / ``xla_compile_seconds_total`` (every
    ``xla.compile``), ``compile_cache_hits_total`` /
    ``compile_cache_misses_total``, ``jax_trace_seconds_total`` /
    ``jax_lower_seconds_total`` (plain sums: a nested trace counts in its
    own and in the one around it)."""
    global _COMPILE_LISTENER_ON
    with _DEFAULT_LOCK:
        if _COMPILE_LISTENER_ON:
            return
        _COMPILE_LISTENER_ON = True
    import jax

    from .registry import default_registry

    asked = threading.local()  # .cache: this thread's compile in progress

    def on_event(event: str, **kw) -> None:
        if event == _CACHE_REQUEST:
            # JAX asks whenever caching is enabled, a directory or not
            asked.cache = {"cache": "miss"} \
                if jax.config.jax_compilation_cache_dir else None
        elif event == _CACHE_HIT and getattr(asked, "cache", None):
            asked.cache["cache"] = "hit"

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == _CACHE_RETRIEVAL and getattr(asked, "cache", None):
            asked.cache["retrieval_s"] = round(duration, 6)
            return
        if event not in _JAX_EVENTS:
            return
        end = time.monotonic_ns()
        name, seconds_total = _JAX_EVENTS[event]
        registry = default_registry()
        registry.counter(seconds_total).inc(duration)
        attrs = {}
        if name == "xla.compile":
            attrs = getattr(asked, "cache", None) or {"cache": "off"}
            asked.cache = None
            registry.counter("xla_compiles_total").inc()
            if attrs["cache"] != "off":
                registry.counter(
                    "compile_cache_hits_total" if attrs["cache"] == "hit"
                    else "compile_cache_misses_total").inc()
        elif duration < _MIN_TRACE_SPAN_S:
            return
        default_tracer().record_complete(
            name, end - int(duration * 1e9), end,
            fun_name=str(kw.get("fun_name", "?")), **attrs)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


# -- `ldt trace` CLI ---------------------------------------------------------


def _load_span_events(paths: List[str], out) -> List[dict]:
    """Merge span JSONL files into one event list (undecodable lines are
    reported and skipped; missing files are reported — a silently dropped
    host's spans read as "that host did nothing" in Perfetto)."""
    events: List[dict] = []
    missing = []
    for path in paths:
        if not os.path.exists(path):
            missing.append(path)
            continue
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, start=1):
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    out.write(
                        f"ldt trace: skipping undecodable line "
                        f"{path}:{lineno}\n"
                    )
    if missing:
        out.write(
            f"ldt trace: missing span file(s): {', '.join(missing)}\n"
        )
    return events


def trace_main(argv=None, out=None) -> int:
    """``ldt trace export`` / ``ldt trace critical-path``.

    * ``export`` merges span JSONLs (written by any process running with
      ``LDT_TRACE_PATH``) into ONE Perfetto-loadable Chrome-trace JSON:
      per-process clocks rebased onto the wall timeline via the
      ``ldt.clock_sync`` anchors, cross-process batch chains stitched
      with flow arrows (``obs/critpath.py``), ring-buffer drop counts
      reported.
    * ``critical-path`` analyzes the same merged events into per-batch
      segment attribution + a straggler table.

    Returns the process exit status."""
    import argparse
    import sys

    out = out if out is not None else sys.stdout
    p = argparse.ArgumentParser(
        prog="ldt trace",
        description="Merge and analyze recorded span JSONLs",
    )
    sub = p.add_subparsers(dest="command")
    exp = sub.add_parser("export", help="convert span JSONL → Chrome trace")
    cp = sub.add_parser(
        "critical-path",
        help="per-batch segment attribution + straggler table",
    )
    for sp in (exp, cp):
        sp.add_argument(
            "--spans", action="append", default=None, metavar="JSONL",
            help="span JSONL file(s) written under LDT_TRACE_PATH "
                 "(repeatable; default: $LDT_TRACE_PATH or "
                 "ldt-spans.jsonl)",
        )
    exp.add_argument("--out", default="ldt-trace.json",
                     help="output Chrome-trace JSON path")
    cp.add_argument("--costs", default=None, metavar="JSONL",
                    help="cost-ledger JSONL (LDT_COST_PATH) to join the "
                         "straggler table against")
    cp.add_argument("--top", type=int, default=10,
                    help="slowest chains to show (default 10)")
    args = p.parse_args(list(argv) if argv is not None else None)
    if args.command not in ("export", "critical-path"):
        p.print_help(out)
        return 2
    from .critpath import (
        critical_path_main,
        dropped_spans,
        flow_events,
        rebase_events,
    )

    spans_paths = args.spans or [
        os.environ.get("LDT_TRACE_PATH", "ldt-spans.jsonl")
    ]
    events = _load_span_events(spans_paths, out)
    if not events:
        out.write(
            "ldt trace: no events collected — run with "
            "LDT_TRACE_PATH=<file> to record spans\n"
        )
        return 2
    if args.command == "critical-path":
        return critical_path_main(events, out, costs_path=args.costs,
                                  top=args.top)
    rebased, offsets = rebase_events(events)
    flows = flow_events(rebased)
    dropped = dropped_spans(events)
    with open(args.out, "w") as f:
        json.dump(chrome_trace(rebased + flows), f)
        f.write("\n")
    out.write(
        f"ldt trace: wrote {len(rebased)} events (+{len(flows)} flow "
        f"arrows, {len(offsets)} process clocks aligned) to {args.out} — "
        "open it at https://ui.perfetto.dev or chrome://tracing\n"
    )
    if dropped:
        out.write(
            f"ldt trace: source ring buffers dropped ~{dropped} spans — "
            "the merged trace is truncated (raise SpanTracer capacity "
            "or rely on the JSONL, which never drops)\n"
        )
    return 0
