"""Closed-loop pipeline autotuning — the first subsystem that writes BACK
into the pipeline it observes.

The obs/ subsystem (r4) measures every stage of the data plane; this
package closes the loop (ROADMAP open item "self-tuning pipeline"): a
per-process :class:`~.controller.AutoTuner` thread snapshots windowed
deltas of those histograms, attributes the bottleneck (decode-bound vs
transport-bound vs H2D-bound vs train-bound), and actuates live knobs
registered as :class:`~.tunable.Tunable`\\ s — decode worker count
(``WorkerPool.resize``), prefetch depth (all loaders), buffer-pool page
budget, placement ring depth, fleet stripe width. Actuation changes
*capacity*, never content: the batch stream stays bit-identical in value
and order through any decision (pinned by the parity tests and
``scripts/autotune_smoke.py``), and ``--no_autotune`` runs the exact fixed-knob
pipeline of r8 and earlier.

Decisions are deterministic and testable: set ``LDT_AUTOTUNE_TRACE=<path>``
and every tick's (window, knobs, bounds, decisions) lands in a JSONL trace
that :func:`~.controller.verify_trace` replays against a fresh policy.

The fleet half lives in ``fleet/``: DataServices report windowed pressure
in heartbeats, the Coordinator aggregates it into a scale-up/drain
recommendation on ``/metrics`` + ``/healthz`` + ``ldt fleet recommend``.
"""

from .controller import (  # noqa: F401
    TRACE_ENV,
    AutoTuner,
    derive_window,
    replay_trace,
    verify_trace,
)
from .policy import (  # noqa: F401
    BOTTLENECK_CODES,
    Decision,
    HillClimbPolicy,
    PolicyConfig,
)
from .tunable import AdjustableQueue, Tunable, collect_tunables  # noqa: F401

__all__ = [
    "AutoTuner",
    "AdjustableQueue",
    "BOTTLENECK_CODES",
    "Decision",
    "HillClimbPolicy",
    "PolicyConfig",
    "TRACE_ENV",
    "Tunable",
    "collect_tunables",
    "derive_window",
    "replay_trace",
    "verify_trace",
]
