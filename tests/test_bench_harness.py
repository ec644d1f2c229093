"""bench.py evidence hardening: transient backend failures retry with
backoff, a final failure still emits a parseable BENCH json record, and
a backend that cannot initialize fails the run — the bench never
switches platform."""

import json

import pytest

import bench


def test_run_guarded_retries_transient_then_succeeds():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: TPU backend setup/compile "
                               "error (socket closed)")
        return {"metric": "m", "value": 1.0}

    out = bench._run_guarded("m", flaky, attempts=3, base_delay=2.0,
                             sleep=sleeps.append)
    assert out == {"metric": "m", "value": 1.0}
    assert calls["n"] == 3
    assert sleeps == [2.0, 4.0]          # exponential backoff


def test_run_guarded_final_failure_emits_parseable_record(capsys):
    def always_down():
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    with pytest.raises(SystemExit) as ei:
        bench._run_guarded("llama", always_down, attempts=3,
                           sleep=lambda _s: None)
    assert ei.value.code == 1
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])            # LAST stdout line is the record
    assert rec["metric"] == "llama"
    assert rec["failed"] is True
    assert rec["failure_class"] == "backend_unavailable"
    assert rec["attempts"] == 3
    assert rec["value"] is None


def test_run_guarded_nontransient_fails_fast_with_class(capsys):
    sleeps = []

    def broken():
        raise ValueError("bad config: vocab mismatch")

    with pytest.raises(SystemExit):
        bench._run_guarded("bert", broken, attempts=3, sleep=sleeps.append)
    assert sleeps == []                  # no pointless backoff
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["failure_class"] == "ValueError"
    assert rec["attempts"] == 1
    assert "vocab mismatch" in rec["error"]


class _Dev:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_ensure_backend_ok_records_the_device():
    bench._ensure_backend(devices_fn=lambda: [_Dev()])
    assert bench._BACKEND["status"] == "ok"
    assert bench._BACKEND["platform"] == "tpu"
    assert bench._BACKEND["device_kind"] == "TPU v5 lite"
    assert bench._BACKEND["count"] == 1


def test_ensure_backend_unavailable_fails_instead_of_switching_platform(
        monkeypatch, capsys, tmp_path):
    """Backend init raises UNAVAILABLE inside the first jax.devices():
    the bench must end in the structured failure line and a non-zero
    exit — never retry on another platform and print a CPU number under
    a device metric's name."""
    import jax

    calls = {"n": 0}

    def devices():
        calls["n"] += 1
        raise RuntimeError(
            "Unable to initialize backend 'tpu': UNAVAILABLE: TPU "
            "backend setup/compile error (Unavailable).")

    platforms = jax.config.jax_platforms
    monkeypatch.setattr(jax, "devices", devices)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    assert calls["n"] == 1               # one probe, no second platform
    assert jax.config.jax_platforms == platforms
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "backend_init"
    assert rec["failed"] is True and rec["value"] is None
    assert rec["failure_class"] == "backend_unavailable"


def test_ensure_backend_fatal_init_error_propagates():
    with pytest.raises(ValueError, match="not a backend problem"):
        bench._ensure_backend(
            devices_fn=lambda: (_ for _ in ()).throw(
                ValueError("not a backend problem")))


@pytest.mark.slow
@pytest.mark.serving
def test_bench_serve_contract():
    """`python bench.py --serve` (the small CPU profile): rc=0, the LAST
    stdout line is a parseable record whose continuous-vs-static
    comparison carries tokens/s, occupancy, p50/p99 latency and dispatch
    counts — and continuous batching beats static batching on tokens/s
    and useful-token occupancy (the engine itself hard-asserts
    per-request greedy parity and the dispatch accounting)."""
    import subprocess
    import sys

    r = subprocess.run([sys.executable, "bench.py", "--serve"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    s = rec["serve"]
    for side in ("continuous", "static"):
        for k in ("tokens_per_sec", "occupancy_useful", "latency_p50_s",
                  "latency_p99_s", "dispatches"):
            assert s[side][k] is not None, (side, k)
    assert s["continuous"]["prefill_dispatches"] == s["requests"]
    assert s["continuous_beats_static"] is True, s


@pytest.mark.slow
def test_bench_decode_emits_modes_breakdown():
    """`python bench.py --decode` contract: final stdout json carries
    tokens/s + dispatch counts + tokens_per_dispatch for every
    mode/batch — plain modes fuse into 2 dispatches per generate,
    speculative modes into 3 (the extra draft prefill) and additionally
    report the mean acceptance length."""
    import subprocess
    import sys

    r = subprocess.run([sys.executable, "bench.py", "--decode"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    modes = rec["decode"]["modes"]
    assert any(k.startswith("greedy_b") for k in modes)
    assert any(k.startswith("greedy_eos_b") for k in modes)
    assert any(k.startswith("sampled_b") for k in modes)
    assert any(k.startswith("spec_greedy_b") for k in modes)
    assert any(k.startswith("spec_sampled_b") for k in modes)
    spec = rec["decode"]["speculative"]
    assert spec["k"] >= 1 and spec["draft"]
    for name, row in modes.items():
        expected = 3 if name.startswith("spec_") else 2
        assert row["dispatches_per_generate"] == expected, name
        assert row["tokens_per_sec"] > 0
        assert row["tokens_per_dispatch"] > 0
        if name.startswith("spec_"):
            assert 0.0 <= row["acceptance_len_mean"] <= spec["k"]
            assert row["num_speculative_tokens"] == spec["k"]
