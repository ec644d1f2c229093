"""What every runner needs: the compile cache, the device check, the watch
for compilations inside the window, the profiler around a stretch, device
memory, the per-layer readers and the labelled output lines."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

from benchmark.harness import resolve, trace as tr

CHECKOUT = os.path.dirname(resolve.ROOT)


def say(label: str, obj) -> None:
    """A labelled line of output, before the last line. Never a metric."""
    print(f"# {label}: {json.dumps(obj)}", flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names where the environment sets it (JAX
    reads the variable itself), else ``<checkout>/.jax_cache``. Every
    program is cached, however quick its compile, so a cell's second run
    compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_devices(chips: int, rehearse: bool):
    """The devices to run on, or None (with the reason on stderr) where a
    measured run may not start: no TPU, or fewer chips than the cell asks
    for. A rehearsal runs on whatever is there and says so."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        print(f"benchmark: no TPU (JAX found {devices[0].platform}); a "
              f"measured run needs the chip. --rehearse runs a tiny width "
              f"for the benchmark's own tests.", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


class CompileWatch:
    """Counts backend compilations by the time they ended (JAX's own
    monitoring events), so that a window can assert it held none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if name == self.EVENT:
            self.times.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class Profiler:
    """The JAX profiler over one stretch, with the benchmark's window
    annotation inside it. ``close_window`` ends the traced stretch without
    stopping the profiler: stopping it stalls the host for tens of seconds
    while the device trace is collected (my chip runs, PR 24), so a loop
    that still has requests in flight stops it only when they are done;
    what the profiler records after the window is ignored by the reducer.
    ``load`` reads and reduces the trace. The Python tracer is off: it
    writes an event per Python call, which makes the trace huge and slows
    the host it is measuring."""

    def __init__(self, name: str, rehearse: bool = False):
        self.rehearse = rehearse
        base = os.environ.get("TMPDIR") or os.path.join(CHECKOUT,
                                                        ".bench_tmp")
        self.dir = os.path.join(base, f"bench_trace_{name}")
        self.on = self.done = False       # on: the traced stretch is open
        self._span = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._span.__enter__()
        self.on = True

    def close_window(self) -> None:
        self._span.__exit__(None, None, None)
        self.on, self.done = False, True

    def stop(self) -> None:
        import jax
        if self.on:
            self.close_window()
        jax.profiler.stop_trace()

    def load(self, keep: str = None) -> dict:
        """The reduced trace; ``keep`` also writes what is in the trace
        (``describe.json``) and the first second of its events
        (``events.json.gz``) there, for looking at one by hand."""
        path = tr.find_xplane(self.dir)
        out = tr.load_xplane(path, cpu_rehearsal=self.rehearse)
        if keep:
            import gzip
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "describe.json"), "w") as f:
                json.dump(tr.describe(path), f, indent=1, default=str)
            t0, _ = tr.window(out)
            cut = {"devices": {
                d: {k: [e for e in v if t0 <= e[1] < t0 + 1e9]
                    for k, v in lines.items()}
                for d, lines in out["devices"].items()},
                "host": [e for e in out["host"]
                         if e[0] == tr.WINDOW_SPAN or t0 <= e[1] < t0 + 1e9]}
            with gzip.open(os.path.join(keep, "events.json.gz"), "wt") as f:
                json.dump(cut, f, separators=(",", ":"))
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the allocator reports
    it (0 where the backend keeps no statistics, as the CPU does)."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def device_line(devices, rehearse: bool, trace: dict = None) -> dict:
    d0 = devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices),
           "memory_peak_bytes": memory_peak_bytes(devices)}
    if rehearse:
        out["rehearsal"] = True
    if trace is not None:
        b = tr.busy_seconds(trace)
        out["busy_s"], out["window_s"] = b["busy_s"], b["window_s"]
    return out


def read_layer_metrics(cell: dict, ctx: dict, root: str,
                       lenient: bool = False) -> dict:
    """{name: value} of the cell's per-layer metrics, each by the reader
    its own file names. A reader that finds nothing returns None and the
    metric is left out; one that raises fails the run (``lenient``, for a
    rehearsal on a trace with no device lines: says so and leaves it out)."""
    out = {}
    for m in cell["layer_metric_files"]:
        reader = resolve.load_module("readers", m["reader"], root)
        try:
            v = reader.read(ctx, **m.get("args", {}))
        except tr.TraceError as e:
            if not lenient:
                raise
            say("rehearsal_left_out", {"metric": m["name"], "why": str(e)})
            v = None
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
