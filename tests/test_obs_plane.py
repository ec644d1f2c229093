"""Live telemetry plane + the serving step measured from inside.

The load-bearing properties:
- the exporter serves /metrics (Prometheus text incl. every attached
  registry + the tracer-saturation gauge), /statusz (strict JSON with
  the engine's slot table / queue / ladder rung) and /tracez (recent
  spans), binds an ephemeral port and RELEASES it on stop;
- the four phases of ``ServingEngine.step`` tile it (always-on
  histograms), show up in a ``jax.profiler`` trace as host annotations
  inside the caller's, and the counts taken at the same boundaries are
  right: live KV positions per chunk, a request's first token from
  submit, backend compiles by dispatch site;
- the device's timeline as the engine knows it tiles wall time between
  two ``metrics()`` calls, puts a host delay in the part it fell in
  (a callback's in ``starved.harvest``, the caller's in
  ``starved.outside``, an idle engine's in ``no_work``), counts one fed
  interval a dispatch, and keeps and logs an interval of a second;
- TTFT/TPOT histograms and per-class SLO violation counters are
  correct on a deterministic serve run;
- the flight recorder dumps a postmortem JSON (spans + resilience
  timeline + metrics + attached registries) when the decode ladder
  exhausts under fault injection;
- empty histograms report NaN percentiles / null snapshot quantiles
  and OMIT the p50/p99 lines from Prometheus exposition (dashboards
  must never read "no data" as "0 ms p99"), while samples_dropped is
  exported first-class.
"""

import json
import math
import os
import urllib.error
import urllib.request

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


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5).read()


# -- exporter ----------------------------------------------------------------

def test_exporter_endpoints_and_port_release(obs_on, dec):
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    for i in range(3):
        eng.submit(np.arange(3 + i) % 64, 4, seed=i)
    eng.drain()
    port = eng.start_exporter(port=0)
    assert port > 0
    assert eng.start_exporter(port=0) == port       # idempotent
    try:
        # /metrics: Prometheus shape, engine registry included, tracer
        # saturation exported first-class
        txt = _get(port, "/metrics").decode()
        assert "# TYPE obs_tracer_dropped_spans gauge" in txt
        assert "serving_prefill_dispatches 3" in txt
        assert "serving_request_latency_s_count 3" in txt
        # /statusz: strict JSON (no NaN literals survive), schema
        raw = _get(port, "/statusz").decode()
        st = json.loads(raw)
        assert "NaN" not in raw
        assert st["pid"] == os.getpid()
        assert st["obs"]["enabled"] is True
        assert st["backend"]["device_count"] >= 1
        sv = st["serving"]
        assert sv["num_slots"] == 2 and sv["queue_depth"] == 0
        assert len(sv["slots"]) == 2
        assert all(s["state"] == "free" for s in sv["slots"])
        assert sv["resilience"]["ladder_rung"] == "chunked"
        # /tracez: recent spans with the dispatch sites, limit honored
        tz = json.loads(_get(port, "/tracez?limit=500"))
        names = {s["name"] for s in tz["spans"]}
        assert "decode.admit_prefill" in names
        assert "decode.chunk" in names
        one = json.loads(_get(port, "/tracez?limit=1"))
        assert len(one["spans"]) == 1
        with pytest.raises(urllib.error.HTTPError):
            _get(port, "/nope")
    finally:
        eng.stop_exporter()
    # stopped: the socket no longer accepts, and the port can be
    # re-bound by a fresh exporter (SO_REUSEADDR server semantics)
    with pytest.raises(OSError):
        _get(port, "/metrics")
    exp2 = obs.ObsExporter(port=port)
    assert exp2.start() == port
    exp2.stop()


def test_exporter_status_provider_errors_stay_in_band(obs_on):
    exp = obs.ObsExporter(port=0)
    exp.add_status_provider("boomy", lambda: 1 / 0)
    port = exp.start()
    try:
        st = json.loads(_get(port, "/statusz"))
        assert "ZeroDivisionError" in st["boomy"]["error"]
    finally:
        exp.stop()


# -- the serving step measured from inside -----------------------------------

# (prompt length, budget) of the requests of one deterministic serve on
# 2 slots x chunk 4: requests 0 and 1 take the slots, 0 finishes after
# its second chunk and 2 refills its slot
SERVED = [(3, 6), (4, 10), (5, 8)]


def _engine(dec, mode, **kw):
    from paddle_tpu.serving import ServingEngine
    if mode == "host_scatter":      # the cache keeps the legacy admission
        kw.update(prefix_cache=True, prefix_block_tokens=4)
    return ServingEngine(dec, num_slots=2, chunk_size=4, **kw)


@pytest.fixture(scope="module", params=["ring", "host_scatter"])
def served(request, dec):
    """One serve per admission mode: the engine's metrics before and
    after, the results, and the wall time of the step() calls."""
    import time
    eng = _engine(dec, request.param)
    assert bool(eng._ring_slots) == (request.param == "ring")
    m0 = eng.metrics()
    rids = [eng.submit(np.arange(P) % 64, N, seed=i)
            for i, (P, N) in enumerate(SERVED)]
    wall, steps = 0.0, 0
    while len(eng.scheduler) or eng.scheduler.slots.occupied():
        t0 = time.monotonic()
        eng.step()
        wall += time.monotonic() - t0
        steps += 1
    t0 = time.monotonic()
    eng.step()                      # an idle step: admit only
    wall += time.monotonic() - t0
    return {"m0": m0, "m1": eng.metrics(), "wall": wall,
            "steps": steps + 1, "eng": eng,
            "records": [eng.result(r).resilience["serving"] for r in rids]}


def test_step_phases_tile_the_step(served):
    m0, m1 = served["m0"], served["m1"]
    ph = dict(m1["step_phase_s"])
    assert list(ph) == ["admit", "dispatch", "wait", "harvest",
                        "admit_wait"]
    # admit_wait is no fifth phase but the part of admit spent blocked
    # on the ring's row-key readback: one interval a ring admission
    blocked = ph.pop("admit_wait")
    ring = bool(served["eng"]._ring_slots)
    assert blocked["count"] == (len(SERVED) if ring else 0)
    assert 0 < blocked["sum"] < ph["admit"]["sum"] if ring \
        else blocked["sum"] == 0
    # every step admits; only a step with an occupied row goes on
    assert m0["step_phase_s"]["admit"]["count"] == 0
    assert ph["admit"]["count"] == served["steps"]
    for name in ("dispatch", "wait", "harvest"):
        assert ph[name]["count"] == m1["chunk_dispatches"] \
            == served["steps"] - 1
    total = sum(v["sum"] for v in ph.values())
    assert total <= served["wall"]
    assert total >= 0.9 * served["wall"], (total, served["wall"])
    # the registry a Prometheus scrape reads carries the same sums
    txt = served["eng"].registry.to_prometheus()
    assert f"serving_step_phase_s_wait_count {ph['wait']['count']}" in txt
    assert "serving_chunk_live_kv_positions" in txt


def test_first_token_from_submit_is_queue_plus_ttft(served):
    for r in served["records"]:
        assert r["first_token_s"] == pytest.approx(
            r["queue_delay_s"] + r["ttft_s"], abs=1e-9)
        assert r["first_token_s"] >= r["ttft_s"] > 0
    # request 2 waited a whole chunk for a slot: only the histogram from
    # submit sees that
    assert served["records"][2]["queue_delay_s"] > 0
    m = served["m1"]
    assert m["ttft_from_submit_p99_s"] >= m["ttft_p99_s"]
    assert m["ttft_from_submit_p50_s"] == pytest.approx(
        sorted(r["first_token_s"] for r in served["records"])[1])


def test_live_kv_positions_match_a_hand_count(served):
    # a row's position at the start of its k-th chunk is its prompt
    # length + 4 k; requests 0, 1, 2 ride 2, 3, 2 chunks
    want = (3 + 7) + (4 + 8 + 12) + (5 + 9)
    m = served["m1"]
    assert [r["chunks"] for r in served["records"]] == [2, 3, 2]
    assert m["live_kv_positions_total"] == want
    # rows a chunk: occupancy_mean x samples x slots, as the reader has it
    rows = m["occupancy_mean"] * m["occupancy_samples"] * m["num_slots"]
    assert rows == pytest.approx(7)
    assert served["m0"]["live_kv_positions_total"] == 0


# -- the device's timeline as the engine knows it ----------------------------

STARVED = ("starved.admit", "starved.dispatch", "starved.harvest",
           "starved.outside")


def _timeline_delta(m0, m1):
    return {p: m1["device_timeline_s"][p] - m0["device_timeline_s"][p]
            for p in m1["device_timeline_s"]}


@pytest.fixture(params=["ring", "host_scatter"])
def warm(request, dec):
    """An engine of each admission mode that has served once: nothing
    compiles from here on."""
    eng = _engine(dec, request.param)
    eng.submit(np.arange(3) % 64, 6)
    eng.drain()
    return eng


def test_device_timeline_tiles_wall_time(warm):
    import time
    reads = []

    def clock():                 # metrics() brings the open part up to
        reads.append(time.monotonic())      # a reading of this clock
        return reads[-1]
    warm._timeline._clock = clock
    m0 = warm.metrics()
    t0 = reads[-1]
    assert list(m0["device_timeline_s"]) == [
        "fed.chunk", "fed.prefill", *STARVED, "no_work"]
    for i, (P, N) in enumerate(SERVED):
        warm.submit(np.arange(P) % 64, N, seed=i)
        time.sleep(0.002)
    while len(warm.scheduler) or warm.scheduler.slots.occupied():
        warm.step()
        time.sleep(0.001)
    m1 = warm.metrics()
    d = _timeline_delta(m0, m1)
    assert all(v >= 0 for v in d.values()) and d["fed.chunk"] > 0
    assert sum(d.values()) == pytest.approx(reads[-1] - t0, rel=1e-6)
    # the registry a Prometheus scrape reads carries the same seconds
    txt = warm.registry.to_prometheus()
    for name in ("serving_device_fed_s_chunk", "serving_device_no_work_s",
                 "serving_device_starved_s_outside"):
        assert f"# TYPE {name} counter" in txt, name


def test_a_delay_lands_in_the_part_it_fell_in(warm):
    """0.05 s slept in a token callback is the harvest phase's, between
    two steps the caller's, and with nothing submitted nobody's."""
    import time
    calls = []

    def slow(rid, new, final):
        calls.append(final)
        time.sleep(0.05)
    m0 = warm.metrics()
    warm.submit(np.arange(3) % 64, 8, on_tokens=slow)
    warm.drain()
    m1 = warm.metrics()
    d = _timeline_delta(m0, m1)
    chunks = m1["chunk_dispatches"] - m0["chunk_dispatches"]
    assert chunks == 2 and calls == [False, True]
    assert d["starved.harvest"] >= 0.05 * chunks
    assert all(d[p] < 0.04 for p in STARVED if p != "starved.harvest"), d
    assert d["no_work"] < 0.04
    assert m1["step_phase_s"]["harvest"]["max"] >= 0.05
    # the caller's time: a request waits while nobody steps
    warm.submit(np.arange(3) % 64, 4)
    time.sleep(0.05)
    warm.drain()
    m2 = warm.metrics()
    d = _timeline_delta(m1, m2)
    assert d["starved.outside"] >= 0.05 and d["no_work"] < 0.04
    assert all(d[p] < 0.04 for p in STARVED if p != "starved.outside"), d
    # and nobody's: nothing is submitted that is unfinished
    time.sleep(0.05)
    warm.step()                              # an idle step changes nothing
    d = _timeline_delta(m2, warm.metrics())
    assert d["no_work"] >= 0.05
    assert all(d[p] < 0.04 for p in STARVED), d
    assert d["fed.chunk"] == d["fed.prefill"] == 0


def test_one_fed_interval_a_dispatch(served):
    m = served["m1"]
    n = m["device_timeline_n"]
    assert n["fed.prefill"] == m["prefill_dispatches"] == len(SERVED)
    assert n["fed.chunk"] == m["chunk_dispatches"]
    assert served["m0"]["device_timeline_n"]["fed.chunk"] == 0
    # every phase's longest interval is one of its intervals
    for ph, v in m["step_phase_s"].items():
        if v["count"]:
            assert v["sum"] / v["count"] <= v["max"] <= v["sum"], ph


@pytest.mark.faults
def test_per_token_rung_is_one_fed_interval_a_step(dec):
    from paddle_tpu.runtime.resilience import fault_injector
    eng = _engine(dec, "ring")
    set_flags({"resilience_backoff_s": 0.0})
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.chunk", "call": 1,
                               "times": 1000}])
    try:
        eng.submit(np.arange(3) % 64, 8)
        eng.drain()
    finally:
        fault_injector.clear()
        set_flags({"resilience_backoff_s": 0.5})
    m = eng.metrics()
    assert m["chunk_dispatches"] == 0 and m["step_dispatches"] == 8
    assert m["device_timeline_n"]["fed.chunk"] == 8
    assert m["device_timeline_n"]["fed.prefill"] == 1
    # between two steps of the rung the host is back in dispatch
    assert m["device_timeline_n"]["starved.dispatch"] == 8


def test_an_interval_of_a_second_is_kept_and_logged_once(
        warm, monkeypatch, caplog):
    """A clock the test moves (no sleep of a second): 2 s pass inside a
    token callback, so one harvest interval is a stall."""
    import logging
    import time
    import types

    import paddle_tpu.obs.trace as trace_mod
    ahead = [0.0]
    shim = types.SimpleNamespace(
        monotonic=lambda: time.monotonic() + ahead[0],
        monotonic_ns=lambda: time.monotonic_ns() + int(ahead[0] * 1e9))
    monkeypatch.setattr(trace_mod, "time", shim)
    warm._timeline._clock = shim.monotonic

    def stall(rid, new, final):
        if not final:
            ahead[0] += 2.0
    assert warm.metrics()["device_timeline_long"] == []
    warm.submit(np.arange(3) % 64, 8, on_tokens=stall)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving"):
        warm.drain()
        m = warm.metrics()
        m_again = warm.metrics()
    (kept,) = m["device_timeline_long"]
    assert kept["part"] == "starved.harvest" and 2.0 <= kept["seconds"] < 3.0
    assert kept["serial"] <= sum(m["device_timeline_n"].values())
    assert m_again["device_timeline_long"] == [kept]
    said = [r for r in caplog.records if "device timeline" in r.getMessage()]
    assert len(said) == 1 and "starved.harvest" in said[0].getMessage()
    assert m["step_phase_s"]["harvest"]["max"] >= 2.0
    assert m["step_phase_s"]["wait"]["max"] < 1.0


@pytest.mark.parametrize("mesh", [None, "tp:2"])
def test_compiles_are_credited_to_the_dispatch_site(mesh):
    """A new admission bucket compiles once under decode.admit_prefill,
    the first chunk once under decode.chunk; the same shapes again
    compile nothing anywhere — under a mesh too, where a ring buffer
    born off the mesh once made the first-warmed bucket compile again
    mid-serving (the counter is what found it)."""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    fresh = LlamaDecoder(LlamaForCausalLM(LlamaConfig(**CFG)), max_len=64,
                         mesh=mesh)
    eng = _engine(fresh, "ring")
    base = obs.compile_counts()

    def serve(P):
        eng.submit(np.arange(P) % 64, 4)
        eng.drain()
        now = eng.metrics()["compiles"]
        assert set(now) <= set(obs.compile_counts())
        return {k: v - base.get(k, 0) for k, v in now.items()}

    c0 = serve(3)
    assert c0["decode.admit_prefill"] == 1 and c0["decode.chunk"] == 1
    c1 = serve(3)
    assert c1 == c0                            # a repeat compiles nothing
    c2 = serve(33)                             # a bucket not seen before
    assert c2["decode.admit_prefill"] == 2 and c2["decode.chunk"] == 1
    assert serve(33) == c2 and serve(3) == c2


def test_profiler_trace_holds_the_phases_inside_the_callers_span(
        dec, tmp_path):
    """A profiler session anyone started sees serving.step.* as host
    annotations on the trace's own clock, nested in the caller's."""
    import glob

    import jax
    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("jax.profiler.ProfileData unavailable")
    eng = _engine(dec, "ring")
    eng.submit(np.arange(3) % 64, 6)
    eng.drain()                                # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    except Exception as e:                     # pragma: no cover
        pytest.skip(f"jax.profiler unavailable: {e}")
    try:
        eng.submit(np.arange(3) % 64, 6)
        with jax.profiler.TraceAnnotation("caller.engine_step"):
            eng.step()
        with obs.span("obs.disabled_span"):    # obs off: no annotation
            pass
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    assert paths, "the profiler wrote no .xplane.pb"
    ev = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("serving.", "caller.", "obs.")):
                        ev[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    if "caller.engine_step" not in ev:
        pytest.skip("this backend's profiler records no host annotations")
    lo, hi = ev["caller.engine_step"]
    order = ["serving.step.admit", "serving.step.dispatch",
             "serving.step.wait", "serving.step.harvest"]
    for name in order:
        assert lo <= ev[name][0] and ev[name][1] <= hi, name
    # one after the other, in the step's order
    assert [n for n, _ in sorted(((n, ev[n][0]) for n in order),
                                 key=lambda kv: kv[1])] == order
    for a, b in zip(order, order[1:]):
        assert ev[a][1] <= ev[b][0]
    # the prefill enqueue and the row-key readback sit inside admit
    a0, a1 = ev["serving.step.admit"]
    for name in ("serving.admit.prefill_enqueue", "serving.admit.row_key"):
        assert a0 <= ev[name][0] and ev[name][1] <= a1, name
    assert "obs.disabled_span" not in ev


# -- SLO instruments ---------------------------------------------------------

def test_ttft_tpot_and_slo_counters(obs_on, dec):
    """Deterministic serve run: every finished request observes TTFT
    once; every multi-token request observes TPOT; the per-request
    record carries both plus the SLO verdict; impossible targets
    violate, generous targets don't."""
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(
        dec, num_slots=2, chunk_size=4,
        slo_targets={"strict": {"ttft_s": 0.0, "latency_s": 0.0},
                     "loose": {"ttft_s": 3600.0, "latency_s": 3600.0}})
    rng = np.random.default_rng(3)
    strict = [eng.submit(rng.integers(0, 64, (4,)), 6, seed=i,
                         latency_class="strict") for i in range(2)]
    loose = [eng.submit(rng.integers(0, 64, (4,)), 6, seed=9,
                        latency_class="loose")]
    single = [eng.submit(rng.integers(0, 64, (4,)), 1, seed=7)]
    res = eng.drain()
    n = len(strict) + len(loose) + len(single)
    h_ttft = eng.registry.get("serving.ttft_s")
    h_tpot = eng.registry.get("serving.tpot_s")
    assert h_ttft.count == n
    assert h_tpot.count == n - 1          # the 1-token request has none
    for rid in strict + loose:
        rec = res[rid].resilience["serving"]
        assert rec["ttft_s"] > 0
        assert rec["tpot_s"] > 0
        assert rec["ttft_s"] <= rec["latency_s"]
    # impossible targets: every strict request violates both ways
    r = eng.registry
    assert r.get("serving.slo.strict.requests").value == len(strict)
    assert r.get("serving.slo.strict.ttft_violations").value \
        == len(strict)
    assert r.get("serving.slo.strict.latency_violations").value \
        == len(strict)
    # generous targets: no loose violations, but the class is counted
    assert r.get("serving.slo.loose.requests").value == len(loose)
    assert r.get("serving.slo.loose.ttft_violations") is None
    assert res[loose[0]].resilience["serving"]["slo"] == {
        "class": "loose", "violated": False,
        "ttft_target_s": 3600.0, "latency_target_s": 3600.0}
    # no targets for the default class: no slo block, no counters
    assert res[single[0]].resilience["serving"]["slo"] is None
    assert r.get("serving.slo.default.requests") is None
    m = eng.metrics()
    assert m["slo_violations"] == 2 * len(strict)
    assert m["ttft_p50_s"] > 0 and m["tpot_mean_s"] > 0
    # per-request SLO override beats the class default
    eng2 = ServingEngine(dec, num_slots=2, chunk_size=4)
    rid = eng2.submit(np.arange(4) % 64, 4, slo_latency_s=0.0)
    eng2.drain()
    assert eng2.registry.get(
        "serving.slo.default.latency_violations").value == 1


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_dumps_on_ladder_exhaustion(obs_on, dec,
                                                    tmp_path):
    from paddle_tpu.runtime.resilience import (DecodeFailedError,
                                               fault_injector)
    set_flags({"obs_flight_dir": str(tmp_path),
               "resilience_retries": 0, "resilience_backoff_s": 0.0})
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.*", "call": 1,
                               "times": 999}])
    try:
        with pytest.raises(DecodeFailedError):
            dec.generate(np.arange(4)[None] % 64, max_new_tokens=4)
    finally:
        fault_injector.clear()
        set_flags({"obs_flight_dir": "", "resilience_retries": 3,
                   "resilience_backoff_s": 0.5})
    dumps = sorted(tmp_path.glob("postmortem_*.json"))
    assert dumps, "ladder exhaustion produced no postmortem"
    pm = json.loads(dumps[-1].read_text())   # strict JSON round-trips
    assert pm["kind"] == "paddle_tpu.postmortem"
    assert pm["reason"] == "decode.ladder_exhausted"
    assert pm["error"]["class"] == "InjectedFault"
    assert pm["extra"]["site"] == "decode.generate"
    # the evidence: the span ring, the typed resilience timeline (the
    # injected faults fire BEFORE a span opens — a failed dispatch
    # never ran — so the faults live in the timeline, not error spans),
    # and the metrics snapshot
    assert isinstance(pm["spans"], list)
    assert pm["spans_in_ring"] >= len(pm["spans"])
    kinds = {e.get("kind") for e in pm["resilience_events"]}
    assert "fault" in kinds and "degradation" in kinds
    assert any(e.get("site", "").startswith("decode.")
               for e in pm["resilience_events"])
    assert "resilience.faults_injected" in pm["metrics"]


def test_flight_recorder_disabled_without_obs(dec, tmp_path):
    set_flags({"obs_enabled": False})
    assert obs.flight_recorder.dump("nope") is None
    # explicit path forces a dump even when disabled (operator ask)
    p = obs.flight_recorder.dump("forced",
                                 path=str(tmp_path / "pm.json"))
    assert p and json.loads((tmp_path / "pm.json").read_text())[
        "reason"] == "forced"


# -- empty-histogram semantics (the no-data-is-not-zero satellite) -----------

def test_empty_histogram_reports_nan_not_zero():
    h = MetricsRegistry().histogram("lat_s", buckets=[0.1, 1.0])
    assert math.isnan(h.percentile(50))
    assert math.isnan(h.percentile(99))
    snap = h.snapshot()
    assert snap["p50"] is None and snap["p99"] is None
    assert snap["mean"] is None and snap["count"] == 0
    h.observe(0.05)
    snap = h.snapshot()
    assert snap["p50"] == 0.05 and snap["mean"] == pytest.approx(0.05)


def test_prometheus_omits_quantiles_when_empty_exports_drops():
    r = MetricsRegistry()
    empty = r.histogram("empty_s", buckets=[0.1])
    full = r.histogram("full_s", buckets=[0.1])
    full.observe(0.05)
    txt = r.to_prometheus()
    assert "empty_s_p50" not in txt and "empty_s_p99" not in txt
    assert "full_s_p50 0.05" in txt and "full_s_p99 0.05" in txt
    # saturation is first-class exposition for every histogram
    assert "empty_s_samples_dropped 0" in txt
    assert "full_s_samples_dropped 0" in txt
    # snapshot carries samples_dropped too (registry-snapshot surface)
    assert r.snapshot()["full_s"]["samples_dropped"] == 0


def test_engine_metrics_nan_before_first_sample(dec):
    """A fresh engine's percentile keys answer NaN (not a fake-fast 0)
    until the first request finishes — and the /statusz JSON path
    sanitizes them to null."""
    from paddle_tpu.obs.exporter import json_safe
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    m = eng.metrics()
    assert math.isnan(m["request_latency_p50_s"])
    assert math.isnan(m["ttft_p99_s"])
    safe = json_safe(m)
    assert safe["request_latency_p50_s"] is None
    json.dumps(safe, allow_nan=False)      # strict-JSON clean
