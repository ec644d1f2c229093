"""The reducer: exact arithmetic on hand-made events, then on a small trace
recorded on the chip (``data/chip_trace_small.json.gz``: a stretch of a
``mistral7b.serve.chat-steady`` traced window, PR 24; the expected numbers
beside it were computed by another route, a sweep over sorted endpoints)."""

import gzip
import json
import os

import pytest

from benchmark.harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _trace(ops, modules=(), host=(), window=(0.0, 100.0)):
    return {"devices": {0: {"ops": [list(e) + [""] * (4 - len(e))
                                    for e in ops],
                            "modules": [list(e) + [""] for e in modules]}},
            "host": [[tr.WINDOW_SPAN, window[0], window[1] - window[0], ""]]
            + [list(h) + [""] for h in host]}


def test_busy_union_and_idle():
    t = _trace([("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 95, 20)])
    b = tr.busy_seconds(t)
    # [0,15] + [30,40] + [95,100] (clipped to the window) = 30 of 100 ns
    assert b["busy_s"] == pytest.approx(30e-9)
    assert b["window_s"] == pytest.approx(100e-9)


def test_self_time_takes_nested_ops_out():
    evs = [("while.1", 0, 100, ""), ("fusion.1", 10, 30, ""),
           ("fusion.2", 50, 20, ""), ("copy.3", 120, 5, "")]
    st = dict(tr.self_times(evs))
    assert st == {"while.1": 50, "fusion.1": 30, "fusion.2": 20, "copy.3": 5}
    t = _trace(evs, window=(0.0, 200.0))
    top = tr.top_device_ops(t)
    assert top[0] == ["fusion", pytest.approx(50e-9)]
    assert ["while", pytest.approx(50e-9)] in top


def test_module_runs_and_kernel_inside_modules():
    t = _trace(
        ops=[("custom-call.1", 12, 4, "pallas decode_attention"),
             ("custom-call.1", 32, 6, "pallas decode_attention"),
             ("custom-call.9", 70, 5, "pallas decode_attention")],
        modules=[("jit_chunk(1)", 10, 10), ("jit_chunk(1)", 30, 10),
                 ("jit_other(2)", 65, 20)])
    assert tr.module_runs(t, "^jit_chunk") == [10, 10]
    total, runs = tr.ops_inside_modules(t, "decode_attention", "^jit_chunk")
    assert (total, runs) == (10, 2)
    assert tr.ops_inside_modules(t, "no_such_kernel", "^jit_chunk") == (0, 2)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    t = _trace(ops=[("a", 0, 10), ("b", 40, 10), ("c", 90, 10)],
               host=[("bench.engine_step", 0, 45), ("bench.sleep", 50, 38)])
    gaps = dict(tr.idle_gaps(t))
    assert gaps["bench.engine_step"] == pytest.approx(30e-9)   # [10,40]
    assert gaps["bench.sleep"] == pytest.approx(40e-9)         # [50,90]


def test_window_annotation_is_required():
    t = _trace([("a", 0, 1)])
    t["host"] = []
    with pytest.raises(tr.TraceError, match="bench.trace_window"):
        tr.window(t)


# -- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(os.path.join(DATA, "chip_trace_small.json.gz"), "rt") as f:
        t = json.load(f)
    t["devices"] = {int(k): v for k, v in t["devices"].items()}
    return t


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "chip_trace_small.expected.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_and_modules(chip_trace, expected):
    b = tr.busy_seconds(chip_trace)
    assert b["window_s"] == pytest.approx(expected["window_s"])
    assert b["busy_s"] == pytest.approx(expected["busy_s"])
    assert 0 < b["busy_s"] < b["window_s"]
    chunks = tr.module_runs(chip_trace, "^jit_ring_chunk_decode")
    prefills = tr.module_runs(chip_trace, "^jit_ring_admit_prefill")
    assert len(chunks) == expected["chunk_runs"]
    assert len(prefills) == expected["prefill_runs"]
    assert sum(chunks) == pytest.approx(expected["chunk_ns"])


def test_recorded_trace_named_kernel_found_and_missing_one_raises(
        chip_trace, expected):
    from benchmark.readers import kernel_step_time
    ctx = {"trace": chip_trace, "section": {"chunk_size": 16}}
    v = kernel_step_time.read(ctx, kernel="decode_attention",
                              module="^jit_ring_chunk_decode",
                              steps_per_run="chunk_size")
    assert v == pytest.approx(expected["decode_attn_step_ms"])
    with pytest.raises(tr.TraceError, match="no_such_kernel"):
        kernel_step_time.read(ctx, kernel="no_such_kernel",
                              module="^jit_ring_chunk_decode")


def test_recorded_trace_breakdown(chip_trace):
    ops = tr.top_device_ops(chip_trace)
    gaps = tr.idle_gaps(chip_trace)
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert all(s > 0 for _, s in ops) and ops == sorted(
        ops, key=lambda kv: -kv[1])
    idle = tr.busy_seconds(chip_trace)
    assert sum(s for _, s in gaps) == pytest.approx(
        idle["window_s"] - idle["busy_s"], rel=1e-6)
