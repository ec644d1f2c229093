"""chip_smoke.py rehearsed in-process on the forced CPU mesh, the
compile-cache directory helper, and the one-process-per-chip refusal."""

import json
import os

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]


@pytest.fixture
def placed_cache(monkeypatch, tmp_path):
    """A cache directory placed from outside: the helper then sets none in
    code, so the rehearsal leaves this process's jax config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    return str(tmp_path)


@pytest.mark.parametrize("chips,phases", [
    (1, ["probe", "train", "serve"]),
    (4, ["probe", "train_sharded", "serve_sharded"]),
])
def test_rehearse_runs_every_phase_and_reports_the_platform(
        capsys, placed_cache, chips, phases):
    import jax

    rc = chip_smoke.main(["--rehearse", "--chips", str(chips)])
    out = _lines(capsys)
    assert rc == 0, out
    assert [o.get("phase") for o in out[:-1]] == phases
    assert all(o["ok"] for o in out)
    assert out[0]["compile_cache_dir"] == placed_cache
    # the last line, and nothing more in it; the platform is the true one
    assert out[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    if chips == 4:
        four = out[1]["four_devices"]
        assert four["program"]["collective_ops"] > 0
        assert all(p["devices"] == 4 for p in four["largest_params"].values())
        assert out[2]["four_devices"]["kv_carry"]["devices"] == 4


def test_default_run_without_a_tpu_fails_and_prints_no_result(
        capsys, placed_cache):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_a_failed_phase_cannot_end_in_exit_code_zero(
        capsys, placed_cache, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("phase broke")

    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    monkeypatch.setattr(chip_smoke, "phase_serve",
                        lambda *a, **k: {"ok": True})
    assert chip_smoke.main(["--rehearse"]) == 1
    out = _lines(capsys)
    assert out[1]["phase"] == "train" and out[1]["ok"] is False
    assert "phase broke" in out[1]["error"]
    assert out[-1]["ok"] is False


def test_compile_cache_dir_placed_from_outside_or_fixed_under_checkout(
        monkeypatch, tmp_path):
    import jax

    from paddle_tpu.runtime.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert updates == []                 # env set: the code sets no other
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)]


def test_cluster_launchers_refuse_a_parent_that_holds_the_tpu(
        monkeypatch, tmp_path):
    """The multi-process modes are CPU drills: from a parent whose backend
    is the TPU they refuse before anything is spawned or written."""
    import jax

    from paddle_tpu.serving.cluster import frontend_proc, launch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for start in (launch.launch_cluster, frontend_proc.launch_worker_pool):
        with pytest.raises(RuntimeError, match="CPU drills"):
            start(None, str(tmp_path / "w"))
    assert not (tmp_path / "w").exists()
