"""Observability: metrics registry, span tracing, batch lineage, exporters.

The telemetry layer the BASELINE north-star metric ("<2% of step time
blocked on the loader") needs once the pipeline is disaggregated: ad-hoc
counters can say *that* a stall happened, only end-to-end attribution can
say *where it was born* — fragment read vs decode vs queue vs wire vs H2D.

* :mod:`.registry` — thread-safe counters / gauges / fixed-bucket
  histograms (p50/p95/p99 by bucket interpolation, bounded memory), one
  process-wide :func:`~.registry.default_registry` every layer meets in;
* :mod:`.spans` — monotonic-clock span tracer (ring buffer, parent ids)
  with Chrome-trace/Perfetto export (``ldt trace export``) and
  ``jax.profiler.TraceAnnotation`` passthrough;
* :mod:`.lineage` — per-batch ``(batch_seq, created_ns, stage_timings)``
  stamps carried through the data plane (and the service wire, versioned +
  backward compatible), closed into ``batch_age_ms``/``wire_ms``/
  ``queue_wait_ms``/``decode_ms`` histograms at the consumer;
* :mod:`.http` — stdlib ``/metrics`` (Prometheus text) + ``/healthz``
  exporter (``--metrics_port`` on ``serve-data`` and ``train``).

Deliberately dependency-free (stdlib only; jax is optional) so decode-only
service hosts carry the same telemetry as trainers.

Robustness series (r8, recorded by ``utils/checkpoint.py`` /
``utils/signals.py`` / ``utils/retry.py`` into the default registry):

* ``ckpt_save_ms`` — histogram of checkpoint save dispatch (+ commit wait
  for awaited emergency saves);
* ``ckpt_last_success_step`` — gauge: the newest persisted absolute step
  (stale vs ``trainer_step_ms_count`` = the save plane is wedged);
* ``trainer_preemptions_total`` — counter: SIGTERM (or chaos) drains;
* ``retry_attempts_total`` — counter: reconnect retries across ALL
  subsystems (client connects, fleet resolves/dials) after unification in
  ``utils/retry.py``.

Autotune series (r9, recorded by ``tune/`` into the default registry):
``autotune_ticks_total`` / ``autotune_decisions_total`` /
``autotune_reverts_total`` counters, ``autotune_knob_<name>`` gauges, and
``autotune_bottleneck`` (coded attribution — README "Autotune"); the fleet
half adds ``fleet_pressure_stall_pct_max``/``_mean`` and
``fleet_scale_recommendation`` on the coordinator. :class:`RegistryDelta`
is the windowed view the controller (and bench scripts) read — deltas
since the previous call, histogram percentiles over the window's own
bucket increments.

Batch-cache series (r13, recorded by ``data/cache.py`` into the default
registry — README "Batch cache" for the full glossary):
``cache_hit_total`` / ``cache_miss_total`` / ``cache_disk_hit_total`` /
``cache_store_total`` / ``cache_spill_total`` / ``cache_evict_total`` /
``cache_torn_total`` / ``cache_spill_errors_total`` counters, the
``cache_ram_bytes`` / ``cache_disk_bytes`` / ``cache_ram_entries`` /
``cache_disk_entries`` occupancy gauges, the ``cache_lookup_ms``
histogram, and the HBM replay tier's ``cache_device_batches`` gauge +
``cache_device_replay_epochs_total`` counter.

Ragged-token series (r15, recorded by ``data/token_pack.py`` /
``ops/token_device.py`` — README "Ragged token plane" for the full
glossary): ``pack_payload_tokens_total`` vs ``pack_grid_tokens_total``
(real vs processed tokens; their window ratio is ``pad_waste_pct`` /
``pack_occupancy`` in the autotune signal dict — emitted by the padded
control arm too, so the waste cut is measured, not assumed),
``pack_sequences_total`` / ``pack_batches_total`` /
``pack_truncated_tokens_total`` counters, ``pack_new_shapes_total``
(fresh pack-kernel jit traces — the recompile cost the
``pack_rows_quantum`` policy rung trades against waste), the sampled
``pack_device_ms`` histogram, and the buffer plane's
``bufpool_ragged_leases_total`` / ``bufpool_ragged_slack_bytes_total``
(capacity-bucket overhead). ``decode_token_bytes_total`` /
``decode_token_copies_total`` are the token path's LDT701 copy-hygiene
rows: bytes leaving decode vs bytes that could not take the zero-copy
Arrow view.

Protocol series (r14 — README "Protocol"):

* ``svc_proto_malformed_hello`` — counter: HELLOs rejected at the type
  gate (``protocol.hello_malformed``) with a skew-style MSG_ERROR — a
  mixed-version or corrupted peer sending a wrong-typed field, answered
  diagnosably instead of a handler-killing ValueError;
* ``fleet_leave_generation`` — gauge: the lease-table generation a
  member's graceful deregister produced (its last fleet fact);
* the opt-in wire witness (``LDT_WIRE_SANITIZER=1``,
  ``utils/wiretrack.py``) records off-registry — per-(msg, field) wire
  counts feed ``ldt check --wire-witness``, not ``/metrics``.

Causal-tracing & SLO series (r18 — README "Causal tracing & SLOs"):

* :mod:`.tracectx` — W3C-style ``(trace_id, parent_span_id)`` context
  stamped at plan-item decode, riding the protocol-v5 batch meta so one
  batch's decode → send → merge → step chain reconstructs across
  processes (``ldt trace export`` draws the parent edges);
* :mod:`.costs` — per-item cost ledger (ring-buffered; ``LDT_COST_PATH``
  JSONL; ``ldt costs report``) keyed by the BatchCache content hash:
  ``cost_records_total`` / ``cost_bytes_total`` / ``cost_reencode_total``
  counters plus ``cost_decode_ms`` / ``cost_entropy_ms`` /
  ``cost_token_len`` histograms;
* :mod:`.critpath` — per-batch dominant-segment attribution + straggler
  table (``ldt trace critical-path``);
* :mod:`.slo` — declared SLOs (``LDT_SLOS``) with multi-window burn-rate
  gauges: ``slo_<name>`` + ``slo_<name>_burn_<window>`` on ``/metrics``,
  ``slo`` block on ``/healthz``; the fleet half aggregates member
  heartbeat histograms into ``fleet_queue_wait_p50/p95/p99_ms``;
* ``spans_dropped_total`` — counter: spans evicted from a full tracer
  ring (the export prints the merged dropped count).
"""

from .http import MetricsHTTPServer, build_info  # noqa: F401
from .lineage import (  # noqa: F401
    make_lineage,
    observe_local_lineage,
    observe_wire_lineage,
)
from .registry import (  # noqa: F401
    DEFAULT_MS_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RegistryDelta,
    default_registry,
    percentile_from_counts,
    render_prometheus,
)
from .costs import (  # noqa: F401
    CostLedger,
    cost_context,
    default_ledger,
    note_cost,
)
from .critpath import analyze as critpath_analyze  # noqa: F401
from .slo import SLO, DEFAULT_SLOS, SLOTracker, parse_slos  # noqa: F401
from .spans import (  # noqa: F401
    Span,
    SpanTracer,
    chrome_trace,
    default_tracer,
    end_phase,
    phase,
    span,
    watch_xla_compiles,
)
from .tracectx import (  # noqa: F401
    child,
    coerce_trace,
    make_trace,
    new_span_id,
    new_trace_id,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsHTTPServer",
    "RegistryDelta",
    "DEFAULT_MS_BUCKETS",
    "default_registry",
    "percentile_from_counts",
    "render_prometheus",
    "Span",
    "SpanTracer",
    "chrome_trace",
    "default_tracer",
    "span",
    "phase",
    "end_phase",
    "watch_xla_compiles",
    "make_lineage",
    "observe_wire_lineage",
    "observe_local_lineage",
    "build_info",
    "CostLedger",
    "cost_context",
    "default_ledger",
    "note_cost",
    "critpath_analyze",
    "SLO",
    "DEFAULT_SLOS",
    "SLOTracker",
    "parse_slos",
    "child",
    "coerce_trace",
    "make_trace",
    "new_span_id",
    "new_trace_id",
]
