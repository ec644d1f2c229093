"""Unified observability spine (paddle_tpu/obs).

The load-bearing properties:
- span nesting, attrs and both exporters round-trip (Chrome JSON loads
  back with the right events; JSONL lines rebuild the spans);
- the metrics registry snapshot + Prometheus text have the contracted
  shape (cumulative buckets, sum/count, get-or-create identity);
- serving timeline completeness: EVERY submitted request shows
  queued -> admitted -> finished events plus a lifetime span, and the
  trace's dispatch-span counts equal the engine's asserted accounting;
- compiled-program cost telemetry attaches FLOPs/bytes to the owning
  jitted-dispatch span (cached per site/signature);
- the DISABLED path adds no measurable per-call work (the near-zero
  overhead contract that lets the instrumentation live on hot paths);
- ``phase`` always feeds its histogram, records a span only with obs
  on, and, like ``span``, shows up once in a live profiler trace;
- ``DeviceTimeline`` holds one part at a time and its parts tile the
  clock: fed from an enqueue's return to the read that waits it out,
  starved where the host is in between, split at the host's phase
  boundaries only while the device is empty; a second in one interval
  is kept and logged once;
- serving latency math is time.monotonic end-to-end (a scheduler-level
  push stamps the submit time itself).
"""

import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.obs as obs
from paddle_tpu.flags import set_flags
from paddle_tpu.obs.metrics import MetricsRegistry

pytestmark = pytest.mark.obs

CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
           num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, max_position_embeddings=64)


@pytest.fixture()
def obs_on():
    set_flags({"obs_enabled": True})
    mark = obs.tracer.mark()
    try:
        yield mark
    finally:
        set_flags({"obs_enabled": False})


@pytest.fixture(scope="module")
def dec():
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    return LlamaDecoder(LlamaForCausalLM(LlamaConfig(**CFG)), max_len=64)


# -- tracer ------------------------------------------------------------------

def test_span_nesting_and_export_roundtrip(obs_on, tmp_path):
    m0 = obs_on
    with obs.span("outer", site="t"):
        with obs.span("inner") as sp:
            sp.annotate(flops=42.0)
            time.sleep(0.002)
    obs.tracer.event("phase.mark", request=7)
    spans = {s.name: s for s in obs.tracer.spans_since(m0)}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["inner"].attrs["flops"] == 42.0
    assert spans["inner"].dur_ms >= 2.0
    assert spans["outer"].dur_ms >= spans["inner"].dur_ms
    assert spans["inner"].start_ns >= spans["outer"].start_ns

    chrome = tmp_path / "t.json"
    obs.tracer.export_chrome_trace(str(chrome), since=m0)
    data = json.loads(chrome.read_text())
    by_name = {e["name"]: e for e in data["traceEvents"]}
    assert by_name["inner"]["ph"] == "X"
    assert by_name["inner"]["args"]["flops"] == 42.0
    assert by_name["phase.mark"]["ph"] == "i"
    assert by_name["inner"]["dur"] == pytest.approx(
        spans["inner"].dur_ms * 1e3)

    jsonl = tmp_path / "t.jsonl"
    obs.tracer.export_jsonl(str(jsonl), since=m0)
    lines = [json.loads(x) for x in jsonl.read_text().splitlines()]
    assert {d["name"] for d in lines} == {"outer", "inner", "phase.mark"}
    inner = next(d for d in lines if d["name"] == "inner")
    assert inner["attrs"]["flops"] == 42.0
    assert inner["parent_id"] == spans["outer"].span_id


def test_span_error_excluded_from_ok_counts(obs_on):
    m0 = obs_on
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("UNAVAILABLE: nope")
    [sp] = obs.tracer.spans_since(m0)
    assert not sp.ok() and "UNAVAILABLE" in sp.attrs["error"]
    assert obs.tracer.counts(m0) == {}
    assert obs.tracer.counts(m0, ok_only=False) == {"boom": 1}


def test_tracer_ring_buffer_bounds(obs_on):
    t = obs.Tracer(capacity=8, enabled=lambda: True)
    for i in range(20):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 8
    assert t.dropped == 12
    assert [s.name for s in t.spans()][-1] == "s19"


def test_disabled_path_near_zero_overhead():
    """The contract that lets span() live inside dispatch wrappers: obs
    off, a span call is one enabled check + a shared no-op context —
    bounded per-call cost, no recording, no allocation growth."""
    set_flags({"obs_enabled": False})
    assert not obs.enabled()
    n = 20000
    # warm both paths
    for _ in range(100):
        with obs.span("x"):
            pass
    t0 = time.perf_counter()
    for _ in range(n):
        pass
    base = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("x"):
            pass
    spent = time.perf_counter() - t0
    per_call = (spent - base) / n
    assert per_call < 20e-6, f"disabled span() costs {per_call*1e6:.2f}µs"
    assert obs.tracer.spans() is not None  # and recorded nothing new
    m = obs.tracer.mark()
    with obs.span("x"):
        pass
    assert obs.tracer.spans_since(m) == []

# -- phase -------------------------------------------------------------------

class _Sum:
    """The least a phase needs of a histogram."""

    def __init__(self):
        self.sum, self.count = 0.0, 0

    def observe(self, v):
        self.sum += v
        self.count += 1


@pytest.mark.parametrize("enabled", [False, True])
def test_phase_feeds_its_histogram_and_records_only_when_enabled(enabled):
    set_flags({"obs_enabled": enabled})
    try:
        h = MetricsRegistry().histogram("p")
        m = obs.tracer.mark()
        with obs.phase("outer.phase", h):
            with obs.phase("inner.phase", h):
                time.sleep(0.002)
        with pytest.raises(ZeroDivisionError):
            with obs.phase("outer.phase", h):
                1 / 0
        assert h.count == 3                  # a raising body still counts
        assert 0.004 <= h.sum < 1.0          # inner + outer, both >= 2 ms
        spans = obs.tracer.spans_since(m)
        if not enabled:
            assert spans == []
            return
        by = {s.name: s for s in spans[:2]}
        assert by["inner.phase"].parent_id == by["outer.phase"].span_id
        assert "error" in spans[2].attrs     # as obs.span records it
    finally:
        set_flags({"obs_enabled": False})


# -- the device timeline, alone, on a clock the test moves -------------------

class _Seconds:
    """What a counter of seconds has to be: ``inc(seconds)``."""

    def __init__(self):
        self.value = 0.0

    def inc(self, s):
        assert s >= 0
        self.value += s


PARTS = ("fed.chunk", "fed.prefill", "starved.admit", "starved.dispatch",
         "starved.harvest", "starved.outside", "no_work")


def _timeline(log=None):
    now = [100.0]
    counters = {p: _Seconds() for p in PARTS}
    tl = obs.DeviceTimeline(counters, log=log, clock=lambda: now[0])

    def at(t=None):                 # move the clock (None: leave it)
        if t is not None:
            now[0] = 100.0 + t
        return now[0]

    def seconds():
        return {p: round(c.value, 9) for p, c in counters.items()
                if c.value}
    return tl, at, seconds


def test_device_timeline_holds_one_part_and_tiles_the_clock():
    tl, at, seconds = _timeline()
    assert tl.part == "no_work"
    tl.host("outside", at(1.0))            # a submit
    tl.host("admit", at(1.5))              # step() begins
    tl.fed("prefill", at(1.7))             # the prefill's enqueue returned
    tl.drained(at(2.4))                    # the row-key readback returned
    tl.host("dispatch", at(2.5))
    tl.fed("chunk", at(2.6))
    tl.host("harvest", at(4.0))            # wait ends: one reading for
    tl.drained(at())                       # the phase and the read
    tl.host(None, at(4.3))                 # step() ends, nothing left
    assert tl.part == "no_work"
    tl.flush(at(5.0))
    got = seconds()
    assert got == {"no_work": 1.7, "starved.outside": 0.5,
                   "starved.admit": 0.3, "fed.prefill": 0.7,
                   "starved.dispatch": 0.1, "fed.chunk": 1.4,
                   "starved.harvest": 0.3}
    assert sum(got.values()) == pytest.approx(5.0, abs=1e-9)
    # one interval each, but admit's two (before and after the prefill)
    assert tl.intervals["starved.admit"] == 2 and tl.serial == 8
    assert tl.intervals["fed.chunk"] == tl.intervals["fed.prefill"] == 1


def test_device_timeline_splits_at_a_phase_boundary_only_while_empty():
    tl, at, seconds = _timeline()
    tl.host("admit", at(0.0))
    tl.host("dispatch", at(0.25))          # empty: admit | dispatch
    tl.fed("chunk", at(0.5))
    tl.host("harvest", at(2.0))            # fed: the chunk is not cut
    tl.host("admit", at(3.0))
    assert tl.part == "fed.chunk" and tl.intervals["fed.chunk"] == 0
    tl.drained(at(3.5))
    assert tl.part == "starved.admit"
    tl.flush(at(4.0))
    assert seconds() == {"starved.admit": 0.75, "starved.dispatch": 0.25,
                         "fed.chunk": 3.0}


def test_device_timeline_double_marks_are_harmless():
    tl, at, seconds = _timeline()
    tl.host("admit", at(0.0))
    tl.drained(at(0.1))                    # empty already: nothing
    tl.drained(at(0.2))
    assert tl.serial == 1 and tl.part == "starved.admit"
    tl.fed("prefill", at(1.0))
    tl.fed("prefill", at(2.0))             # closes one, opens its own
    tl.fed("chunk", at(2.5))               # and so does another kind
    tl.host("admit", at(2.75))
    tl.host("admit", at(2.8))
    tl.drained(at(3.0))
    tl.drained(at(3.5))
    tl.flush(at(4.0))
    assert tl.intervals["fed.prefill"] == 2 and tl.intervals["fed.chunk"] == 1
    assert seconds() == {"starved.admit": 2.0, "fed.prefill": 1.5,
                         "fed.chunk": 0.5}


def test_device_timeline_keeps_and_logs_a_long_interval_once(caplog):
    import logging
    log = logging.getLogger("paddle_tpu.test_timeline")
    tl, at, seconds = _timeline(log)
    with caplog.at_level(logging.WARNING, logger=log.name):
        tl.host("harvest", at(3.0))        # 3 s of no_work: idle, no stall
        tl.flush(at(3.4))                  # a scrape inside the interval
        tl.flush(at(3.9))                  # does not cut it in two
        tl.host("outside", at(4.2))        # 1.2 s in starved.harvest
        tl.fed("chunk", at(4.3))
        tl.drained(at(5.2))                # 0.9 s: not long
        assert tl.long[-1] == {"serial": 2, "part": "starved.harvest",
                               "seconds": pytest.approx(1.2)}
        assert len(tl.long) == 1 and len(caplog.records) == 1
        assert "starved.harvest" in caplog.records[0].getMessage()
        for k in range(40):                # the newest 32 are kept
            tl.fed("prefill", at(10.0 + 2 * k))
    assert len(tl.long) == 32 and tl.long[-1]["part"] == "fed.prefill"
    assert [e["serial"] for e in tl.long] == list(range(13, 45))
    assert obs.trace.LONG_INTERVAL_S == 1.0
    assert sum(seconds().values()) == pytest.approx(88.0)


def test_device_timeline_tiles_under_a_scraping_thread():
    """``metrics()`` flushes from whatever thread scrapes while the engine
    thread marks: no second is lost or counted twice, no counter goes
    back (a lost update would break the tiling)."""
    import sys
    import threading
    counters = {p: _Seconds() for p in PARTS}
    t_made = time.monotonic()
    tl = obs.DeviceTimeline(counters, host="outside")
    stop = threading.Event()

    def scrape():
        while not stop.is_set():
            tl.flush()
    threads = [threading.Thread(target=scrape) for _ in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        end = time.monotonic() + 0.2
        while time.monotonic() < end:
            tl.host("admit", time.monotonic())
            tl.fed("prefill")
            tl.drained(time.monotonic())
            tl.host("dispatch")
            tl.fed("chunk")
            tl.host("harvest")
            tl.drained()
            tl.host("outside")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    tl.flush()
    wall = time.monotonic() - t_made
    total = sum(c.value for c in counters.values())
    assert total <= wall and total == pytest.approx(wall, abs=2e-3)
    assert tl.intervals["fed.chunk"] == tl.intervals["fed.prefill"] > 10


def test_histogram_keeps_its_largest_observation():
    h = MetricsRegistry().histogram("m")
    assert h.max == 0.0
    for v in (0.25, 3.5, 0.5):
        h.observe(v)
    assert h.max == 3.5 and h.count == 3


def test_phase_disabled_path_bounded_cost():
    """phase() sits in ServingEngine.step four times a step with obs
    off: an annotation enter/exit with no profiler session, two clock
    reads and one observe. Bounded per call, and nothing recorded."""
    set_flags({"obs_enabled": False})
    h = _Sum()
    n = 20000
    for _ in range(100):
        with obs.phase("x", h):
            pass
    t0 = time.perf_counter()
    for _ in range(n):
        pass
    base = time.perf_counter() - t0
    m = obs.tracer.mark()
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.phase("x", h):
            pass
    per_call = (time.perf_counter() - t0 - base) / n
    assert per_call < 20e-6, f"disabled phase() costs {per_call*1e6:.2f}µs"
    assert h.count == n + 100
    assert obs.tracer.spans_since(m) == []


def test_span_and_phase_open_one_profiler_annotation_each(obs_on, tmp_path):
    """With obs on, a span is also a host event of the same name in a
    live profiler trace, and a phase (which records a span) is there
    once, not twice."""
    import glob

    import jax
    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("jax.profiler.ProfileData unavailable")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    except Exception as e:                     # pragma: no cover
        pytest.skip(f"jax.profiler unavailable: {e}")
    try:
        with obs.span("t.span", kind="dispatch"):
            with obs.phase("t.phase", _Sum()):
                pass
    finally:
        jax.profiler.stop_trace()
    names = []
    for path in glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                names += [e.name for e in line.events
                          if e.name.startswith("t.")]
    if not names:
        pytest.skip("this backend's profiler records no host annotations")
    assert sorted(names) == ["t.phase", "t.span"]


# -- metrics -----------------------------------------------------------------

def test_metrics_registry_shapes_and_prometheus():
    r = MetricsRegistry()
    c = r.counter("decode.dispatches", "help text")
    c.inc()
    c.inc(2)
    assert r.counter("decode.dispatches") is c     # get-or-create
    with pytest.raises(TypeError):
        r.gauge("decode.dispatches")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("queue.depth")
    g.set(5)
    g.set(2)
    h = r.histogram("lat_s", buckets=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.5, 2.0):
        h.observe(v)

    snap = r.snapshot()
    assert snap["decode.dispatches"] == {"type": "counter", "value": 3}
    assert snap["queue.depth"]["value"] == 2 and \
        snap["queue.depth"]["max"] == 5
    hs = snap["lat_s"]
    assert hs["count"] == 4 and hs["sum"] == pytest.approx(2.555)
    # cumulative prometheus buckets + +Inf tail
    assert hs["buckets"] == {"0.01": 1, "0.1": 2, "1.0": 3, "+Inf": 4}
    assert hs["p50"] == pytest.approx(h.percentile(50))

    txt = r.to_prometheus()
    assert "# TYPE decode_dispatches counter" in txt
    assert "decode_dispatches 3" in txt
    assert "# HELP decode_dispatches help text" in txt
    assert '# TYPE lat_s histogram' in txt
    assert 'lat_s_bucket{le="+Inf"} 4' in txt
    assert "lat_s_count 4" in txt
    assert "lat_s_sum 2.555" in txt


def test_histogram_percentiles():
    h = MetricsRegistry().histogram("h", buckets=[1.0])
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(99) == pytest.approx(99.01)
    assert h.mean == pytest.approx(50.5)


# -- cost telemetry ----------------------------------------------------------

def test_cost_analysis_attaches_to_jitted_dispatch(obs_on, dec):
    """A generate under obs: the prefill/fused dispatch spans carry the
    compiled program's FLOPs (cost_analysis) — the per-dispatch MFU
    numerator — and the obs dispatch counters match dispatch_count."""
    m0 = obs.tracer.mark()
    d0 = dec.dispatch_count
    c0 = {name: obs.metrics.counter(name).value
          for name in ("dispatches.decode.prefill",
                       "dispatches.decode.fused")}
    prompt = np.arange(4)[None] % 64
    dec.generate(prompt, max_new_tokens=6)
    counts = obs.tracer.counts(m0)
    assert counts == {"decode.prefill": 1, "decode.fused": 1}
    assert dec.dispatch_count - d0 == 2           # fused generate = prefill+1
    for name in c0:
        assert obs.metrics.counter(name).value - c0[name] == 1
    spans = {s.name: s for s in obs.tracer.spans_since(m0)}
    cost = obs.site_costs()
    if "decode.fused" not in cost:      # backend without cost_analysis
        pytest.skip("cost_analysis unavailable on this backend")
    assert spans["decode.fused"].attrs["flops"] > 0
    assert spans["decode.prefill"].attrs["flops"] > 0
    assert cost["decode.fused"]["flops"] == \
        spans["decode.fused"].attrs["flops"]
    assert obs.mfu(cost["decode.fused"]["flops"], 0.001, peak=1e12) > 0


def test_dispatch_cost_cached_per_signature():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    a = jnp.ones((16, 16))
    c1 = obs.dispatch_cost("t.sig", f, (a,), {})
    if c1 is None:
        pytest.skip("cost_analysis unavailable on this backend")
    assert c1["flops"] > 0
    assert obs.dispatch_cost("t.sig", f, (a,), {}) == c1   # cache hit
    c2 = obs.dispatch_cost("t.sig", f, (jnp.ones((32, 32)),), {})
    assert c2["flops"] > c1["flops"]               # new signature, new entry


def test_dispatch_cost_is_per_device_under_sharding():
    """The honest-MFU contract at sharded sites: XLA's cost_analysis on
    a PARTITIONED program reports per-partition FLOPs, so the recorded
    ``flops`` must come out close to global/num_devices — NOT the global
    count (which would inflate per-device MFU by the mesh size) — with
    ``num_devices``/``flops_global`` alongside for the global view."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel import ProcessMesh
    mesh = ProcessMesh(shape=(4,), dim_names=("tp",))
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((128, 256))
    b = jnp.zeros((256, 128))
    base = obs.dispatch_cost("t.unsharded", f, (a, b), {})
    if base is None:
        pytest.skip("cost_analysis unavailable on this backend")
    ash = jax.device_put(a, NamedSharding(mesh.jax_mesh, P(None, "tp")))
    bsh = jax.device_put(b, NamedSharding(mesh.jax_mesh, P("tp", None)))
    c = obs.dispatch_cost("t.sharded", f, (ash, bsh), {}, num_devices=4)
    assert c is not None and c["num_devices"] == 4
    # per-partition: global/4 plus the all-reduce — far below global
    assert c["flops"] < base["flops"] * 0.5, (c, base)
    assert c["flops_global"] == c["flops"] * 4


# -- serving timeline --------------------------------------------------------

def test_serving_timeline_complete_and_accounted(obs_on, dec):
    """Every submitted request has queued -> admitted -> finished events
    and a lifetime span; dispatch-span counts equal the engine's
    asserted accounting (one prefill per admitted request + one span per
    chunk); metrics() grows the p50/p99 latency + queue-depth keys while
    keeping every legacy key."""
    from paddle_tpu.serving import ServingEngine
    m0 = obs.tracer.mark()
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rng = np.random.default_rng(11)
    ids = [eng.submit(rng.integers(0, 64, (int(rng.integers(2, 8)),)),
                      int(rng.integers(2, 9)), seed=i) for i in range(5)]
    res = eng.drain()
    assert sorted(res) == ids
    m = eng.metrics()
    counts = obs.tracer.counts(m0)
    assert counts["decode.admit_prefill"] == m["prefill_dispatches"] \
        == len(ids)
    assert counts["decode.chunk"] == m["chunk_dispatches"]
    assert counts["serving.request"] == len(ids)
    events = [s for s in obs.tracer.spans_since(m0) if s.kind == "event"]
    for rid in ids:
        for phase in ("queued", "admitted", "finished"):
            assert any(e.name == f"serving.request.{phase}"
                       and e.attrs.get("request") == rid
                       for e in events), (rid, phase)
    # lifetime spans carry the serving attrs trace_report tabulates
    req_spans = [s for s in obs.tracer.spans_since(m0)
                 if s.name == "serving.request"]
    assert {s.attrs["request"] for s in req_spans} == set(ids)
    assert all(s.attrs["chunks"] >= 1 and s.attrs["queue_delay_s"] >= 0
               for s in req_spans)

    legacy = {"num_slots", "chunk_size", "requests_submitted",
              "requests_completed", "queued", "prefill_dispatches",
              "chunk_dispatches", "step_dispatches", "degradations",
              "occupancy_mean", "occupancy_samples", "slot_steps_total",
              "queue_delay_mean_s", "queue_delay_p50_s",
              "queue_delay_p99_s"}
    assert legacy <= set(m)                      # compatibility shim
    assert m["request_latency_p50_s"] > 0
    assert m["request_latency_p99_s"] >= m["request_latency_p50_s"]
    assert m["request_latency_mean_s"] > 0
    assert m["queue_depth_peak"] >= 0 and m["queue_depth_now"] == 0
    for rid in ids:
        rec = res[rid].resilience["serving"]
        assert rec["latency_s"] >= rec["queue_delay_s"] >= 0.0
        assert rec["latency_s"] < 600.0          # monotonic, not epoch math
    # the engine's registry speaks Prometheus
    txt = eng.registry.to_prometheus()
    assert f"serving_prefill_dispatches {len(ids)}" in txt
    assert "serving_request_latency_s_count 5" in txt


def test_trace_report_renders_serving_trace(obs_on, dec, tmp_path):
    import sys
    sys.path.insert(0, "tools")
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    from paddle_tpu.serving import ServingEngine
    m0 = obs.tracer.mark()
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    for i in range(3):
        eng.submit(np.arange(3 + i) % 64, 4, seed=i)
    eng.drain()
    path = tmp_path / "trace.json"
    obs.tracer.export_chrome_trace(str(path), since=m0)
    assert trace_report.main([str(path)]) == 0
    spans, events = trace_report._load(str(path))
    rows, completeness = trace_report.request_table(spans, events)
    assert len(rows) == 3 and completeness["incomplete"] == []
    phases = {r["phase"] for r in trace_report.phase_table(spans)}
    assert {"decode.admit_prefill", "decode.chunk",
            "serving.request"} <= phases
    assert trace_report.main([str(tmp_path / "missing.json")]) == 1


# -- resilience mirror -------------------------------------------------------

def test_resilience_events_mirror_into_obs_counters(obs_on, dec):
    from paddle_tpu.runtime.resilience import fault_injector
    r0 = obs.metrics.counter("resilience.retries").value
    set_flags({"resilience_backoff_s": 0.0})
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.fused", "call": 1}])
    try:
        dec.generate(np.arange(4)[None] % 64, max_new_tokens=4)
    finally:
        fault_injector.clear()
        set_flags({"resilience_backoff_s": 0.5})
    assert obs.metrics.counter("resilience.retries").value == r0 + 1
    ev = [s for s in obs.tracer.spans()
          if s.kind == "event" and s.name == "resilience.retry"]
    assert ev and ev[-1].attrs["site"] == "decode.fused"


# -- monotonic accounting (the scheduler-level satellite) --------------------

def test_scheduler_push_stamps_monotonic_submit_time():
    from paddle_tpu.serving import Request, Scheduler
    sch = Scheduler(num_slots=1)
    t0 = time.monotonic()
    sch.push(Request(id=0, prompt=np.arange(3), max_new_tokens=2))
    [(slot, req)] = sch.admissions()
    # stamped at push, on the monotonic clock: a queue delay computed
    # against monotonic 'now' is microseconds, not hours
    assert t0 <= req.submit_time <= time.monotonic()
    sch.slots.release(slot)
    explicit = Request(id=1, prompt=np.arange(3), max_new_tokens=2,
                       submit_time=12345.0)
    sch.push(explicit)
    [(_, req2)] = sch.admissions()
    assert req2.submit_time == 12345.0            # caller stamp respected


# -- what a compiled program holds, and the one peaks table -------------------

class _Compiled:
    """The HLO text of a compiled program, as far as the census reads it."""
    _CALL = ('  %c.{i} = bf16[8,128] custom-call(%x), custom_call_target='
             '"tpu_custom_call", metadata={{op_name="{op}" stack_frame_id=1}}')

    def __init__(self, ops, collectives):
        self._lines = [self._CALL.format(i=i, op=op)
                       for i, op in enumerate(ops)]
        self._lines += [f"  %r.{i} = f32[8] {c}(%y), replica_groups={{}}"
                        for i, c in enumerate(collectives)]

    def as_text(self):
        return "\n".join(["HloModule jit_step", *self._lines, "ROOT %t"])


def test_program_census_counts_named_kernels_and_collectives():
    census = obs.program_census(_Compiled(
        ["jit(step)/jvp(rms_norm_fwd)/pallas_call",
         "jit(step)/transpose(jvp(rms_norm_bwd))/pallas_call",
         "jit(step)/transpose(jvp(rms_norm_bwd))/pallas_call",
         "jit(f)/decode_attention/pallas_call",
         "jit(f)/pallas_call"],
        ["all-reduce", "all-reduce-start", "all-gather", "all-reduce-done",
         "collective-permute-start", "add"]))
    assert census["kernels"] == {"rms_norm_fwd": 1, "rms_norm_bwd": 2,
                                 "decode_attention": 1, "unnamed": 1}
    assert census["collectives"] == {"all-reduce": 2, "all-gather": 1,
                                     "collective-permute": 1}


@pytest.mark.parametrize("platform,kind,peak", [
    ("tpu", "TPU v5 lite", 197e12),
    ("tpu", "TPU v4", 275e12),
    ("cpu", "cpu", None),                  # no CPU peak: MFU is a device metric
    ("tpu", "TPU v9 mystery", KeyError),   # not in the table: never a default
])
def test_device_peak_flops_is_one_table_keyed_by_device_kind(
        monkeypatch, platform, kind, peak):
    import jax

    class Dev:
        pass
    Dev.platform, Dev.device_kind = platform, kind
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    if peak is KeyError:
        with pytest.raises(KeyError, match="TPU v9 mystery"):
            obs.device_peak_flops()
        return
    assert obs.device_peak_flops() == peak
    assert obs.mfu(1e12, 1.0) == (None if peak is None else 1e12 / peak)
