"""The reader over the engine's device timeline, on hand-made ``ctx``: the
window's differences per chunk and per window, ``None`` where the program
has no such counters (the parent commit of the PR that added them), a stall
said once. The cell that lists the metrics resolves and its mix draws what
it says (``test_cli.py`` rehearses it, as it does every cell of
``BENCHMARK.json``)."""

import json
import os

import pytest

from benchmark.harness import resolve
from benchmark.traffic._lengths import request_shapes

timeline = resolve.load_module("readers", "device_timeline")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PARTS = ("fed.chunk", "fed.prefill", "starved.admit", "starved.dispatch",
         "starved.harvest", "starved.outside", "no_work")
STARVED = [p for p in PARTS if p.startswith("starved.")]
NEW = ("period_chunk_ms", "period_prefill_ms", "starved_admit_ms",
       "starved_dispatch_ms", "starved_harvest_ms", "starved_outside_ms",
       "starved_share")


def _metrics(chunks, seconds, long=()):
    return {"chunk_dispatches": chunks,
            "device_timeline_s": dict(zip(PARTS, seconds)),
            "device_timeline_long": list(long)}


def _ctx(long0=(), long1=()):
    # a window of 20 chunks and 4.0 s: 2.8 in chunks, 1.0 in prefills,
    # 0.08 + 0.02 + 0.04 + 0.01 starved, 0.05 with no work
    return {"engine": {
        "before": _metrics(10, (5.0, 1.0, 0.5, 0.1, 0.2, 0.0, 9.0), long0),
        "after": _metrics(30, (7.8, 2.0, 0.58, 0.12, 0.24, 0.01, 9.05),
                          long1)}}


def test_parts_per_chunk_and_per_window():
    ctx = _ctx()
    per_chunk = {p: timeline.read(ctx, parts=[p], per="chunk")
                 for p in PARTS}
    assert per_chunk == pytest.approx({
        "fed.chunk": 140.0, "fed.prefill": 50.0, "starved.admit": 4.0,
        "starved.dispatch": 1.0, "starved.harvest": 2.0,
        "starved.outside": 0.5, "no_work": 2.5})
    # the parts tile the window: 4.0 s over 20 chunks
    assert sum(per_chunk.values()) == pytest.approx(200.0)
    assert timeline.read(ctx, parts=STARVED, per="window") \
        == pytest.approx(100 * 0.15 / 4.0)
    assert timeline.read(ctx, parts=list(PARTS), per="window") \
        == pytest.approx(100.0)
    with pytest.raises(ValueError):
        timeline.read(ctx, parts=STARVED, per="step")


def test_the_metric_files_ask_for_it():
    ctx = _ctx()
    want = dict(zip(NEW, (140.0, 50.0, 4.0, 1.0, 2.0, 0.5, 3.75)))
    for name in NEW:
        with open(os.path.join(ROOT, "benchmark/layer_metrics",
                               name + ".json")) as fh:
            f = json.load(fh)
        assert f["reader"] == "device_timeline"
        assert (f["source"], f["moves"]) == ("program_counter", "out_tok_s")
        assert timeline.read(ctx, **f["args"]) == pytest.approx(want[name])


@pytest.mark.parametrize("ctx", [
    {}, {"engine": None},
    # the parent's engine: no device_timeline_s
    {"engine": {"before": {"chunk_dispatches": 1},
                "after": {"chunk_dispatches": 9}}}])
def test_nothing_to_read_without_the_counters(ctx, capsys):
    assert timeline.read(ctx, parts=["fed.chunk"], per="chunk") is None
    assert timeline.read(ctx, parts=STARVED, per="window") is None
    assert capsys.readouterr().out == ""


def test_no_chunk_in_the_window_reads_none_per_chunk():
    m = _metrics(5, (1, 1, 1, 1, 1, 1, 1))
    ctx = {"engine": {"before": m,
                      "after": _metrics(5, (1, 1, 1, 1, 1, 1, 3))}}
    assert timeline.read(ctx, parts=["no_work"], per="chunk") is None
    assert timeline.read(ctx, parts=["no_work"], per="window") \
        == pytest.approx(100.0)


def test_a_stall_is_said_once_and_only_if_new(capsys):
    old = {"serial": 40, "part": "starved.admit", "seconds": 3.5}
    new = {"serial": 977, "part": "fed.chunk", "seconds": 1.25}
    ctx = _ctx(long0=[old], long1=[old, new])
    for name in ("fed.chunk", "fed.prefill", "starved.admit"):
        timeline.read(ctx, parts=[name], per="chunk")
    timeline.read(ctx, parts=STARVED, per="window")
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln.split(":")[0] for ln in lines] == [
        "# device_timeline", "# timeline_stalls"]
    assert json.loads(lines[1].split(": ", 1)[1]) == [new]
    said = json.loads(lines[0].split(": ", 1)[1])
    assert said["window_s"] == pytest.approx(4.0)
    assert said["chunk_dispatches"] == 20
    assert sum(said["ms_per_chunk"].values()) == pytest.approx(200.0)
    # a run with none says so: an empty list, once
    quiet = _ctx()
    timeline.read(quiet, parts=STARVED, per="window")
    timeline.read(quiet, parts=STARVED, per="window")
    out = capsys.readouterr().out
    assert out.count("# timeline_stalls: []") == 1


def test_the_new_cell_resolves_and_its_mix_draws_what_it_says():
    c = resolve.load_cell("mistral7b.serve.longprompt")
    assert (c["config"], c["section"], c["chips"], c["runner"]) == (
        "mistral-7b-v0.3", "serve", 1, "serve")
    assert c["mix"]["generator"] == "closed_loop"
    assert c["end_to_end"] == ["out_tok_s", "setup_s"]
    assert c["per_layer"][-7:] == list(NEW)
    assert [m["reader"] for m in c["layer_metric_files"][-7:]] == [
        "device_timeline"] * 7
    # the section is the accepted backlog cell's: the same memory
    b = resolve.load_cell("mistral7b.serve.backlog")
    assert c["config_file"]["sections"]["serve"] \
        == b["config_file"]["sections"]["serve"]
    max_len = int(c["config_file"]["sections"]["serve"]["max_len"])
    p, o = request_shapes(c["mix"], int(c["mix"]["pool"]))
    assert p.min() >= 1024 and p.max() <= 1792
    assert o.min() >= 16 and o.max() <= 32
    assert p.min() < 1100 and p.max() > 1700 and {16, 32} <= set(o)
    assert (p + o).max() <= 1824 < max_len
