"""The readers over the engine's step counters, on hand-made ``ctx``: the
window's differences, ``None`` where the program has no such counter (the
parent commit of the PR that added them), and the roofline's arithmetic
against a worked number. The cell that reports them resolves
(``test_cli.py`` rehearses it, as it does every cell of ``BENCHMARK.json``)."""

import json
import os

import pytest

from benchmark.harness import resolve
from benchmark.harness.trace import WINDOW_SPAN, TraceError
from benchmark.peaks import peaks

phase_time = resolve.load_module("readers", "phase_time_per_chunk")
roofline = resolve.load_module("readers", "decode_attn_roofline")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _metrics(chunks, admit, dispatch, wait, harvest, live=None,
             blocked=0.0):
    m = {"chunk_dispatches": chunks,
         "step_phase_s": {"admit": {"sum": admit, "count": chunks + 2},
                          "dispatch": {"sum": dispatch, "count": chunks},
                          "wait": {"sum": wait, "count": chunks},
                          "harvest": {"sum": harvest, "count": chunks},
                          "admit_wait": {"sum": blocked,
                                         "count": 2 * chunks}}}
    if live is not None:
        m["live_kv_positions_total"] = live
    return m


def test_phase_time_is_the_windows_difference_per_chunk():
    ctx = {"engine": {"before": _metrics(10, 1.0, 0.1, 3.0, 0.5),
                      "after": _metrics(30, 1.4, 0.12, 10.0, 0.6)}}
    assert phase_time.read(ctx, phases=["admit"]) == pytest.approx(20.0)
    assert phase_time.read(ctx, phases=["harvest"]) == pytest.approx(5.0)
    host = phase_time.read(ctx, phases=["admit", "dispatch", "harvest"])
    assert host == pytest.approx(26.0)       # wait is the device's, left out
    assert phase_time.read(ctx, phases=["wait"]) == pytest.approx(350.0)


def test_phase_time_takes_the_blocked_part_of_admit_off():
    # of the 0.4 s of admit in the window's 20 chunks, 0.3 s is the
    # row-key readback waiting out the prefill: device time, not the host's
    ctx = {"engine": {
        "before": _metrics(10, 1.0, 0.1, 3.0, 0.5, blocked=0.7),
        "after": _metrics(30, 1.4, 0.12, 10.0, 0.6, blocked=1.0)}}
    assert phase_time.read(ctx, phases=["admit"], minus=["admit_wait"]) \
        == pytest.approx(5.0)
    assert phase_time.read(ctx, phases=["admit", "dispatch", "harvest"],
                           minus=["admit_wait"]) == pytest.approx(11.0)
    # as the metric files ask for it
    for name, want in (("step_host_ms", 11.0), ("step_admit_ms", 5.0),
                       ("step_harvest_ms", 5.0)):
        with open(os.path.join(ROOT, "benchmark/layer_metrics",
                               name + ".json")) as fh:
            args = json.load(fh)["args"]
        assert phase_time.read(ctx, **args) == pytest.approx(want), name


@pytest.mark.parametrize("ctx", [
    {}, {"engine": None},
    # the parent's engine: no step_phase_s, no live_kv_positions_total
    {"engine": {"before": {"chunk_dispatches": 1},
                "after": {"chunk_dispatches": 9}}, "trace": {}},
    # no chunk inside the window
    {"engine": {"before": _metrics(5, 1, 1, 1, 1, live=7),
                "after": _metrics(5, 2, 2, 2, 2, live=7)}, "trace": {}}])
def test_readers_find_nothing_without_the_counters(ctx):
    assert phase_time.read(ctx, phases=["admit"]) is None
    assert roofline.read(ctx, kernel="decode_attention",
                         module="^jit_ring_chunk_decode") is None


def _trace(kernel_us, runs=2, calls=24):
    """``runs`` chunk-program runs of 400 ms, each holding ``calls`` kernel
    events of ``kernel_us`` microseconds."""
    mods, ops, t = [], [], 1e6
    for _ in range(runs):
        mods.append(["jit_ring_chunk_decode(123)", t, 4e8, ""])
        for k in range(calls):
            ops.append(["decode_attention.7", t + 1e6 * (k + 1),
                        kernel_us * 1e3, ""])
        t += 5e8
    return {"devices": {0: {"modules": mods, "ops": ops}},
            "host": [[WINDOW_SPAN, 0.0, t + 1e9, ""]]}


ARCH = {"num_key_value_heads": 8, "head_dim": 128}
SECTION = {"num_hidden_layers": 12, "dtype": "bfloat16", "chunk_size": 16,
           "max_len": 2048, "num_slots": 16}


def _ctx(live_per_chunk, kernel_us, calls=16 * 12):
    return {"trace": _trace(kernel_us, calls=calls), "arch": ARCH,
            "section": SECTION, "peaks": peaks("TPU v5 lite"),
            "engine": {"before": _metrics(4, 0, 0, 0, 0, live=1000),
                       "after": _metrics(
                           14, 0, 0, 0, 0, live=1000 + 10 * live_per_chunk)}}


def test_roofline_arithmetic_against_a_worked_number():
    # 16 rows x 450 live positions = 7200 a chunk; a position is 12 layers
    # x (K and V) x 8 heads x 128 x 2 B = 49152 B; 7200 x 49152 =
    # 353,894,400 B a step, / 819e9 B/s = 0.432105 ms. The kernel: 12
    # calls a step x 16 steps a chunk, 350 us each = 4.2 ms a step.
    ctx = _ctx(7200, 350.0)
    got = roofline.read(ctx, kernel="decode_attention",
                        module="^jit_ring_chunk_decode")
    assert got == pytest.approx(100 * 0.432105 / 4.2, rel=1e-5)
    assert got == pytest.approx(10.288, rel=1e-3)
    (note,) = ctx["notes"]
    assert "memory-bound" in note and "7200.0 live positions" in note
    assert "3.539e+08 bytes" in note


def test_roofline_cannot_pass_100_for_positions_within_max_len():
    # the most the counter can read: every row at max_len, and a kernel
    # that streams exactly those bytes at the HBM peak
    full = SECTION["num_slots"] * SECTION["max_len"]
    at_peak_us = full * 49152 / 819e9 / 12 * 1e6     # per call, 12 a step
    got = roofline.read(_ctx(full, at_peak_us), kernel="decode_attention",
                        module="^jit_ring_chunk_decode")
    assert got == pytest.approx(100.0, rel=1e-6)
    # any real kernel also reads the positions the chunk adds: slower
    assert roofline.read(_ctx(full, at_peak_us * 1.01),
                         kernel="decode_attention",
                         module="^jit_ring_chunk_decode") < 100.0


def test_roofline_fails_where_the_kernel_is_not_in_the_program():
    ctx = _ctx(7200, 350.0)
    with pytest.raises(TraceError):
        roofline.read(ctx, kernel="no_such_kernel",
                      module="^jit_ring_chunk_decode")


def test_the_new_cell_resolves_with_its_metric_files():
    b = resolve.load_cell("mistral7b.serve.backlog")
    assert (b["config"], b["chips"], b["mix"]["generator"]) == (
        "mistral-7b-v0.3", 1, "closed_loop")
    assert [m["reader"] for m in b["layer_metric_files"][3:]] == [
        "phase_time_per_chunk"] * 3 + ["decode_attn_roofline"]
