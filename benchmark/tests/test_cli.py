"""The command end to end at the rehearsal width, and the harness's promise
that cells, configurations and per-layer metrics are files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.harness import resolve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _last_line(capsys, argv):
    rc = bench_run.main(argv)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def _declared(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", CELLS)}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_last_line_has_the_contracts_keys(capsys, cell):
    rc, last, _ = _last_line(capsys, ["--workload", cell, "--seed",
                                      str(2**31 + 5), "--seconds", "1.5",
                                      "--trace", "0", "--rehearse"])
    assert rc == 0 and set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == _declared("end_to_end", cell)
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert last["device"]["rehearsal"] is True
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_traced_run(capsys, cell):
    rc, last, _ = _last_line(capsys, ["--workload", cell, "--seed", "3",
                                      "--seconds", "2", "--trace", "1",
                                      "--rehearse"])
    assert rc == 0 and set(last) == KEYS | {"breakdown"}
    # a CPU trace has no module line: those metrics are left out, none is
    # invented, and what is there is declared for this cell
    assert set(last["metrics"]) <= _declared("per_layer", cell)
    assert last["metrics"] and last["device"]["busy_s"] > 0
    assert last["device"]["window_s"] > last["device"]["busy_s"]
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in last["breakdown"].values())


def test_without_a_tpu_no_result_and_nonzero_exit():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("kind,name", [
    ("workloads", "no.such.cell"), ("configs", "no-such-config"),
    ("traffic", "no-such-mix"), ("layer_metrics", "no_such_metric")])
def test_unknown_json_name_is_an_error_naming_the_path(kind, name):
    with pytest.raises(resolve.UnknownName) as e:
        resolve.load_json(kind, name)
    assert os.path.join("benchmark", kind, name + ".json") in str(e.value)


@pytest.mark.parametrize("kind,name", [
    ("traffic", "no_such_generator"), ("readers", "no_such_reader"),
    ("runners", "no_such_runner")])
def test_unknown_module_name_is_an_error_naming_the_path(kind, name):
    with pytest.raises(resolve.UnknownName) as e:
        resolve.load_module(kind, name)
    assert os.path.join("benchmark", kind, name + ".py") in str(e.value)


def test_unknown_cell_on_the_command_line(capsys):
    assert bench_run.main(["--workload", "nope", "--rehearse"]) == 2
    cap = capsys.readouterr()
    assert cap.out == "" and "workloads/nope.json" in cap.err


def test_a_cell_is_added_as_one_json_file(tmp_path, capsys):
    """``mistral7b.serve.backlog`` exists only as one new file in a copy of
    the data directories: it resolves and rehearses with no code."""
    root = tmp_path / "benchmark"
    for d in ("workloads", "configs", "traffic", "layer_metrics", "readers",
              "runners"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d), root / d)
    new = {"config": "mistral-7b-v0.3", "section": "serve",
           "traffic": "backlog", "chips": 1, "runner": "serve",
           "traffic_params": {}, "end_to_end": ["out_tok_s", "setup_s"],
           "per_layer": ["sched_occupancy", "chunk_step_device_ms.tput",
                         "device_idle.serve"],
           "why": "the crossing of cells 1 and 3"}
    (root / "workloads" / "mistral7b.serve.backlog.json").write_text(
        json.dumps(new))
    cell = resolve.load_cell("mistral7b.serve.backlog", str(root))
    assert cell["config_file"]["num_key_value_heads"] == 8
    assert cell["mix"]["generator"] == "closed_loop"
    rc, last, _ = _last_line(capsys, [
        "--workload", "mistral7b.serve.backlog", "--root", str(root),
        "--seed", "8", "--seconds", "1.5", "--trace", "0", "--rehearse"])
    assert rc == 0 and last["correct"] and set(last["metrics"]) == {
        "out_tok_s", "setup_s"}


def test_benchmark_json_agrees_with_the_files():
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = resolve.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        assert set(cell["end_to_end"]) == _declared("end_to_end", w["name"])
        assert set(cell["per_layer"]) == _declared("per_layer", w["name"])
        assert "setup_s" in cell["end_to_end"] and len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        f = resolve.load_json("layer_metrics", m["name"])
        assert all(f[k] == m[k] for k in ("unit", "better", "source",
                                          "layer", "moves"))
        # the metric it moves is reported wherever this one is
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            f = json.load(fh)
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]


def test_benchmark_json_keeps_to_the_contracts_limits():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and c["file"].startswith("benchmark/")
        assert all(name.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in
                       c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    # a full check with all 24 cells fits its 43200 s
    r = BENCH["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200
    for w in BENCH["workloads"]:       # every cell: setup_s, another, a layer
        e = _declared("end_to_end", w["name"])
        assert "setup_s" in e and len(e) >= 2
        assert _declared("per_layer", w["name"])
