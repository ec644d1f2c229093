"""paddle_tpu.obs — unified observability spine.

One telemetry surface shared by decode, serving, resilience, checkpoint
IO and bench:

- :mod:`~paddle_tpu.obs.trace` — thread-safe structured span tracer
  (nested spans, monotonic clocks, bounded ring buffer) with Chrome
  trace and JSONL exporters; ``phase(name, hist)``, the always-on
  primitive behind ``ServingEngine.step``'s admit / dispatch / wait /
  harvest. A span or a phase is also a ``jax.profiler.TraceAnnotation``
  of the same name: a host event on the device trace's own clock
  whenever a profiler session is live, so no merge step is needed;
  ``DeviceTimeline``, the always-on primitive behind the engine's
  ``fed.chunk`` / ``fed.prefill`` / ``starved.<phase>`` / ``no_work``
  seconds: one part open at a time, marked where an enqueue and a
  blocking read return, tiling wall time;
- :mod:`~paddle_tpu.obs.metrics` — typed metrics registry (counters /
  gauges / explicit-bucket histograms) with snapshot + Prometheus text
  export;
- :mod:`~paddle_tpu.obs.cost` — compiled-program cost telemetry:
  ``cost_analysis()`` FLOPs/bytes and ``memory_analysis()`` peak bytes
  attached to the owning dispatch span, so every bench can report
  tokens/s AND MFU per dispatch (Pope et al., 2211.05102 discipline);
  and, always on, backend compiles credited to the dispatch site they
  fell inside (``obs.compiles.<site>``);
- :mod:`~paddle_tpu.obs.exporter` — the live telemetry plane:
  ``/metrics`` (Prometheus), ``/statusz`` (JSON status), ``/tracez``
  (recent spans) on a stdlib HTTP thread
  (``FLAGS_obs_export_port`` / ``PADDLE_TPU_OBS_PORT``);
- :mod:`~paddle_tpu.obs.flight` — the crash flight recorder: last-N
  spans + resilience timeline + metrics snapshot dumped to a
  postmortem JSON when the decode ladder exhausts.

Disabled by default: enable with ``FLAGS_obs_enabled=1`` /
``set_flags({"obs_enabled": True})`` / ``PADDLE_TPU_OBS=1``. The
disabled path is a single enabled check per instrumented call (guarded
by an overhead test). ``tools/trace_report.py`` renders an exported
trace into per-phase / per-request summary tables.

What an operator reads: ``ServingEngine.metrics()`` (``step_phase_s``
with each phase's ``max``, ``device_timeline_s`` — the seconds of
``fed.chunk``, ``fed.prefill``, ``starved.admit`` / ``.dispatch`` /
``.harvest`` / ``.outside`` and ``no_work``, which tile wall time —,
``device_timeline_n``, ``device_timeline_long`` — the intervals of a
second or more, each also a WARNING on the logger
``paddle_tpu.serving`` —, ``live_kv_positions_total``,
``ttft_from_submit_*``, ``compiles`` beside the older keys), the same
instruments on ``/metrics`` (``serving.device.*``), and
``serving.step.*`` / ``serving.admit.*`` in any profiler trace of a
serving run. Device time itself is the benchmark's to measure
(``benchmark/harness/trace.py``): the timeline says what the host
knows, not what ran.
"""

from paddle_tpu.obs.trace import (  # noqa: F401
    DeviceTimeline, Span, Tracer, obs_enabled, phase, span, tracer,
)
from paddle_tpu.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, metrics,
)
from paddle_tpu.obs.cost import (  # noqa: F401
    clear_cost_cache, compile_counts, device_peak_flops, dispatch_cost,
    dispatch_site, mfu, program_census, site_costs, watch_compiles,
)
from paddle_tpu.obs.exporter import (  # noqa: F401
    ObsExporter, resolve_export_port,
)
from paddle_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, flight_recorder, record_crash,
)

__all__ = [
    "Span", "Tracer", "tracer", "span", "phase", "obs_enabled",
    "DeviceTimeline",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "metrics",
    "dispatch_cost", "site_costs", "clear_cost_cache",
    "device_peak_flops", "mfu", "program_census",
    "dispatch_site", "watch_compiles", "compile_counts",
    "ObsExporter", "resolve_export_port",
    "FlightRecorder", "flight_recorder", "record_crash",
    "enabled",
]

# the short form call sites use: ``if obs.enabled():``
enabled = obs_enabled
