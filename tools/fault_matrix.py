"""Fault-matrix runner: sweep every injectable fault class and gate on it.

For each fault class the drill asserts the resilience contract
(ISSUE/README "Robustness"): the system either RECOVERS with bit-exact
output parity vs the no-fault run (and the retry/degradation counters
say how), or raises a TYPED, documented error — never a raw traceback,
never a silent wrong answer.

Classes swept (decode + checkpoint + bundle + elastic + serving paths):
  transient_dispatch    one UNAVAILABLE on the fused decode dispatch ->
                        retried, bit-exact, retries==1, no degradation
  spec_verify_dispatch  speculative decode program dead -> automatic
                        degradation to fused plain decode, bit-exact
                        (greedy), DegradationEvent recorded
  torn_checkpoint       save crashes mid-shard -> reload raises typed
                        CorruptCheckpointError (no silent partial load)
  corrupt_bundle        bit-flipped AOT module bytes -> sha256 manifest
                        refuses it with CorruptBundleError
  dead_elastic          member's heartbeat dies (injected) -> survivor
                        TTL-detects it on the monotonic clock
  replica_kill          one ReplicaSet replica's chunk dispatches die
                        fatally mid-serve -> breaker opens typed, every
                        in-flight/queued request requeues to survivors
                        with its generated tokens replayed, greedy
                        outputs bit-exact vs the undisturbed run
  hung_replica          a replica's heartbeat is delayed (injected
                        skip window) -> router marks it suspect, routes
                        around it, recovers it on the next clean beat;
                        all requests complete bit-exact
  snapshot_torn_write   DecodeState snapshot torn mid-write (injected
                        crash) -> restore refuses typed
                        CorruptCheckpointError; a clean re-snapshot
                        restores and continues generation bit-exactly
  worker_process_kill   a cluster decode worker PROCESS is SIGKILLed
                        mid-run (REAL OS kill, not injection) -> the
                        frontend heartbeat-TTL-detects the death and
                        replays its accepted work onto the survivor
                        bit-exactly — zero lost requests
  frontend_rpc_timeout  a cluster worker HANGS (stalled op on its
                        serial RPC serve thread; heartbeats keep
                        flowing) -> the frontend's step future times
                        out, the breaker opens as a dead socket, the
                        hung worker's work requeues bit-exactly
  migrate_mid_handoff_kill  the migration SOURCE is SIGKILLed between
                        extraction and absorb (REAL OS kill via the
                        _on_extracted drill hook) -> the destination
                        wins: ownership left the source with the
                        payload, so the later death requeues NOTHING
                        (exactly-once) and every request completes
                        bit-exact with zero replays
  rolling_restart_under_load  rolling_restart() cycles every worker of
                        a serving cluster mid-run -> in-flight rows
                        live-migrate to the peer and back, zero worker
                        deaths, zero lost requests, all bit-exact
  frontend_kill_mid_serve  the FRONTEND process is SIGKILLed mid-serve
                        (REAL OS kill, work in flight AND queued) ->
                        a respawned ClusterRouter(resume_wal=...)
                        replays the durable WAL, re-adopts the live
                        workers, recovers every accepted request
                        bit-exact vs the undisturbed run, and the dead
                        incarnation's epoch is fenced typed
                        (StaleEpochError) when it tries to operate
  rpc_partition         an asymmetric network partition drops every
                        frontend->victim RPC message -> the victim's
                        work requeues onto the survivor bit-exact with
                        no double-serve; partitioning the WHOLE decode
                        pool sheds typed (ReplicaDeadError), no hang

Prints one human line per class to stderr and ONE parseable JSON line
to stdout (the bench.py last-line contract); exit code 0 iff all pass.
Usage: python tools/fault_matrix.py
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tiny_decoder(max_len=48):
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64)
    return LlamaDecoder(LlamaForCausalLM(cfg), max_len=max_len)


def drill_transient_dispatch():
    import numpy as np
    from paddle_tpu.runtime.resilience import fault_injector
    dec = _tiny_decoder()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 8))
    ref = dec.generate(prompt, max_new_tokens=6)
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "decode.fused", "call": 1,
                               "times": 1}])
    out = dec.generate(prompt, max_new_tokens=6)
    assert np.array_equal(np.asarray(out), np.asarray(ref)), \
        "retried decode diverged from the no-fault run"
    r = out.resilience
    assert r["retries"] == 1 and not r["degradations"] \
        and r["level"] == "fused", r
    return f"recovered via retry (retries={r['retries']}, bit-exact)"


def drill_spec_verify_dispatch():
    import numpy as np
    from paddle_tpu.runtime.resilience import fault_injector
    dec = _tiny_decoder()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, (2, 8))
    ref = dec.generate(prompt, max_new_tokens=6)   # greedy == spec greedy
    fault_injector.configure([{"kind": "dispatch_error",
                               "site": "spec.decode", "call": 1,
                               "times": 1000}])
    out = dec.generate(prompt, max_new_tokens=6, draft_model="skip:1",
                       num_speculative_tokens=2)
    assert np.array_equal(np.asarray(out), np.asarray(ref)), \
        "degraded speculative decode diverged from the no-fault run"
    r = out.resilience
    assert r["level"] == "fused" and r["degradations"], r
    assert r["degradations"][0]["from_level"] == "speculative"
    return (f"degraded speculative->fused (retries={r['retries']}, "
            f"bit-exact)")


def drill_torn_checkpoint(tmp):
    import numpy as np
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.runtime.resilience import (CorruptCheckpointError,
                                               InjectedFault,
                                               fault_injector)
    w = Tensor(np.arange(64, dtype=np.float32).reshape(8, 8))
    cdir = os.path.join(tmp, "torn_ck")
    fault_injector.configure([{"kind": "torn_write",
                               "path": "data_r0.npz", "at_byte": 64}])
    try:
        ckpt.save_state_dict({"w": w}, cdir)
        raise AssertionError("torn-write injection did not fire")
    except InjectedFault:
        pass                       # the simulated mid-shard crash
    dst = Tensor(np.zeros((8, 8), np.float32))
    try:
        ckpt.load_state_dict({"w": dst}, cdir)
        raise AssertionError("partial checkpoint loaded silently")
    except CorruptCheckpointError as e:
        return f"typed refusal: {str(e)[:80]}"


def drill_corrupt_bundle(tmp):
    import numpy as np
    from paddle_tpu.inference.bundle import (AotPredictor,
                                             export_decoder_bundle)
    from paddle_tpu.runtime.resilience import CorruptBundleError
    dec = _tiny_decoder(max_len=32)
    bdir = os.path.join(tmp, "bundle")
    export_decoder_bundle(dec, bdir, prompt_lens=[4], decode_steps=[4],
                          batch_sizes=[1])
    # silent media corruption: flip one bit inside the baked weights
    victim = next(f for f in sorted(os.listdir(bdir))
                  if f.startswith("decode_") and f.endswith(".aot"))
    fp = os.path.join(bdir, victim)
    blob = bytearray(open(fp, "rb").read())
    blob[len(blob) // 2] ^= 0x01
    with open(fp, "wb") as f:
        f.write(bytes(blob))
    pred = AotPredictor(bdir)
    prompt = np.zeros((1, 4), np.int64)
    try:
        pred.generate(prompt, max_new_tokens=4)
        raise AssertionError("bit-flipped module served silently")
    except CorruptBundleError as e:
        return f"manifest refusal: {str(e)[:80]}"


def drill_dead_elastic():
    from paddle_tpu.distributed.elastic import ElasticManager
    from paddle_tpu.native.tcp_store import TCPStore
    from paddle_tpu.runtime.resilience import fault_injector
    store = TCPStore(is_master=True, world_size=1)
    survivor = ElasticManager(store, "fm-node0", np_range="1:2",
                              heartbeat_s=0.1, ttl_s=0.6)
    victim = ElasticManager(store, "fm-node1", np_range="1:2",
                            heartbeat_s=0.1, ttl_s=0.6)
    fault_injector.configure([{"kind": "dead_heartbeat",
                               "node": "fm-node1", "after_beats": 3}])
    try:
        survivor.start()
        victim.start()
        deadline = time.monotonic() + 20
        saw_both = False
        while time.monotonic() < deadline:
            m = survivor.members
            if sorted(m) == ["fm-node0", "fm-node1"]:
                saw_both = True
            if saw_both and m == ["fm-node0"]:
                return "dead member TTL-detected on the monotonic clock"
            time.sleep(0.05)
        raise AssertionError(
            f"dead member not detected (saw_both={saw_both}, "
            f"members={survivor.members})")
    finally:
        survivor.stop()
        victim.stop()


def _replica_workload(n=6, seed=5, n_replicas=1):
    """A tiny model, ``n_replicas`` decoders over the SAME weights (a
    replica pool serves one model), a mixed workload and its undisturbed
    solo-greedy reference outputs."""
    import numpy as np
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    decs = [LlamaDecoder(model, max_len=64) for _ in range(n_replicas)]
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, 64, (int(rng.integers(2, 10)),)),
             int(rng.integers(6, 14))) for _ in range(n)]
    solo = [np.asarray(decs[0].generate(p[None], n_))
            for p, n_ in reqs]
    return decs, reqs, solo


def drill_replica_kill():
    import numpy as np
    from paddle_tpu.serving import ReplicaSet, Router
    from paddle_tpu.runtime.resilience import fault_injector
    decs, reqs, solo = _replica_workload(n_replicas=3)
    router = Router(ReplicaSet.from_backends(decs, num_slots=2,
                                             chunk_size=4),
                    breaker_threshold=2)
    fault_injector.configure([
        {"kind": "dispatch_error", "site": "serving.replica1.chunk",
         "call": 2, "times": 1000000, "code": "INTERNAL"},
        {"kind": "dispatch_error", "site": "serving.replica1.step",
         "call": 1, "times": 1000000, "code": "INTERNAL"}])
    rids = [router.submit(p, n) for p, n in reqs]
    outs = router.drain()
    for i, rid in enumerate(rids):
        out = outs[rid]
        assert not isinstance(out, BaseException), \
            f"request {i} lost to the dead replica: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged after requeue"
    m = router.metrics()
    assert m["states"]["replica1"] == "dead", m
    assert m["requeued"] >= 1 and m["replica_deaths"] == 1, m
    return (f"breaker opened, {m['requeued']} requests requeued to "
            f"survivors, all {len(reqs)} bit-exact")


def drill_hung_replica():
    import numpy as np
    from paddle_tpu.serving import ReplicaSet, Router
    from paddle_tpu.runtime.resilience import fault_injector
    decs, reqs, solo = _replica_workload(seed=6, n_replicas=2)
    router = Router(ReplicaSet.from_backends(decs, num_slots=2,
                                             chunk_size=4),
                    heartbeat_miss_threshold=2)
    fault_injector.configure([
        {"kind": "delay_heartbeat", "node": "replica1",
         "after_beats": 1, "skip_beats": 4}])
    rids = [router.submit(p, n) for p, n in reqs]
    saw_suspect = False
    outs = {}
    while any(r.has_work() for r in router.replicas.live()):
        for rid, res in router.step():
            outs[rid] = res
        states = {r.name: r.state for r in router.replicas}
        saw_suspect = saw_suspect or states.get("replica1") == "suspect"
    for i, rid in enumerate(rids):
        assert np.array_equal(np.asarray(outs[rid]), solo[i]), \
            f"request {i} diverged under the delayed heartbeat"
    assert saw_suspect, "delayed heartbeat never marked the replica " \
                        "suspect"
    assert router.metrics()["heartbeat_suspects"] >= 1
    # the router loop keeps polling idle replicas in production: a few
    # idle steps let the skip window lapse and the recovery beat land
    for _ in range(8):
        router.step()
    states = {r.name: r.state for r in router.replicas}
    assert states["replica1"] == "healthy", \
        f"replica never recovered after the skip window: {states}"
    return ("suspect during the skip window, recovered on a clean "
            "beat, all requests bit-exact")


def drill_snapshot_torn_write(tmp):
    import numpy as np
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.runtime.resilience import (CorruptCheckpointError,
                                               InjectedFault,
                                               fault_injector)
    decs, reqs, solo = _replica_workload(n=4, seed=7)
    dec = decs[0]
    sdir = os.path.join(tmp, "serve_snap")
    eng = ServingEngine(dec, num_slots=2, chunk_size=4)
    rids = [eng.submit(p, n) for p, n in reqs]
    got = {}
    for _ in range(2):
        for rid, res in eng.step():
            got[rid] = res
    fault_injector.configure([{"kind": "torn_write",
                               "path": "*state.npz", "at_byte": 100}])
    try:
        eng.snapshot(sdir)
        raise AssertionError("torn-write injection did not fire")
    except InjectedFault:
        pass                      # the simulated crash mid-snapshot
    fault_injector.clear()
    fresh = ServingEngine(dec, num_slots=2, chunk_size=4)
    try:
        fresh.restore(sdir)
        raise AssertionError("torn snapshot restored silently")
    except CorruptCheckpointError as e:
        typed = str(e)[:60]
    # the engine is still alive: a clean re-snapshot must restore and
    # continue bit-exactly (recover-bit-exact-OR-typed-error, both arms)
    eng.snapshot(sdir)
    fresh = ServingEngine(dec, num_slots=2, chunk_size=4)
    fresh.restore(sdir)
    got.update(fresh.drain())
    for i, rid in enumerate(rids):
        assert np.array_equal(np.asarray(got[rid]), solo[i]), \
            f"request {i} diverged after snapshot->restore"
    return f"typed refusal ({typed}…), clean re-snapshot bit-exact"


def _cluster_workload(n=5, seed=8):
    """A tiny model for the multi-process drills + its undisturbed
    in-process solo-greedy references (the SAME weights every worker
    process rebuilds from the shipped npz)."""
    import numpy as np
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    dec = LlamaDecoder(model, max_len=48)
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, 64, (6,)), int(rng.integers(6, 12)))
            for _ in range(n)]
    solo = [np.asarray(dec.generate(p[None], n_)) for p, n_ in reqs]
    return model, reqs, solo


def drill_worker_process_kill(tmp):
    import numpy as np
    from paddle_tpu.serving import launch_cluster
    model, reqs, solo = _cluster_workload(seed=8)
    with launch_cluster(model, os.path.join(tmp, "kill_cluster"),
                        prefill=0, decode=2, max_len=48,
                        engine_kw={"num_slots": 2, "chunk_size": 4},
                        heartbeat_s=0.3, ttl_s=2.0,
                        heartbeat_miss_threshold=1,
                        rpc_timeout_s=60.0) as cl:
        router = cl.router
        rids = [router.submit(p, n) for p, n in reqs]
        outs = {}
        for _ in range(2):                   # let work start flowing
            for rid, res in router.step():
                outs[rid] = res
        pid = cl.kill("decode0")             # REAL SIGKILL, no injection
        # let the TTL lapse so the heartbeat sweep (not a long socket
        # timeout) is what sees the death
        time.sleep(2.5)
        outs.update(router.drain())
        m = router.metrics()
    for i, rid in enumerate(rids):
        out = outs.get(rid)
        assert out is not None and not isinstance(out, BaseException), \
            f"request {i} lost to the SIGKILLed worker: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged after the cross-process requeue"
    assert m["states"]["decode0"] == "dead", m
    assert m["worker_deaths"] >= 1 and m["requeued"] >= 1, m
    return (f"SIGKILLed pid {pid} heartbeat-TTL-detected, "
            f"{m['requeued']} requests replayed, all bit-exact")


def drill_frontend_rpc_timeout(tmp):
    import numpy as np
    from paddle_tpu.serving import launch_cluster
    from paddle_tpu.serving.cluster.worker import worker_op
    model, reqs, solo = _cluster_workload(seed=9)
    # ttl_s is LONG on purpose: the hung worker's heartbeat thread keeps
    # beating, so only the dead-socket (RPC timeout) path can catch it
    with launch_cluster(model, os.path.join(tmp, "hang_cluster"),
                        prefill=0, decode=2, max_len=48,
                        engine_kw={"num_slots": 2, "chunk_size": 4},
                        heartbeat_s=0.3, ttl_s=30.0,
                        rpc_timeout_s=60.0) as cl:
        router = cl.router
        rids = [router.submit(p, n) for p, n in reqs]
        outs = {}
        for _ in range(2):                   # compiles land inside the
            for rid, res in router.step():   # generous warmup timeout
                outs[rid] = res
        victim = cl.handle("decode0")
        # fire-and-forget: the stall occupies the worker's SERIAL serve
        # thread, so every later op's future just never resolves
        router.agent.call(victim.rank, worker_op, ("stall", 12.0), {})
        router.rpc_timeout_s = 5.0
        outs.update(router.drain())
        m = router.metrics()
        dead = next(w for w in router.status()["workers"]
                    if w["name"] == "decode0")
        router.rpc_timeout_s = 60.0
    for i, rid in enumerate(rids):
        out = outs.get(rid)
        assert out is not None and not isinstance(out, BaseException), \
            f"request {i} lost to the hung worker: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged after the hung-worker requeue"
    assert m["states"]["decode0"] == "dead", m
    assert m["worker_deaths"] >= 1 and m["requeued"] >= 1, m
    assert dead["last_error"], "dead-socket strike recorded no error"
    return (f"hung worker dead-socket-detected "
            f"({dead['last_error'][:60]}), {m['requeued']} requests "
            f"requeued, all bit-exact")


def drill_migrate_mid_handoff_kill(tmp):
    import numpy as np
    from paddle_tpu.serving import launch_cluster
    model, reqs, solo = _cluster_workload(n=4, seed=10)
    with launch_cluster(model, os.path.join(tmp, "handoff_cluster"),
                        prefill=0, decode=2, max_len=48,
                        engine_kw={"num_slots": 4, "chunk_size": 4},
                        heartbeat_s=0.3, ttl_s=2.0,
                        heartbeat_miss_threshold=1,
                        rpc_timeout_s=60.0) as cl:
        router = cl.router
        rids = [router.submit(p, n) for p, n in reqs]
        for _ in range(2):                   # rows genuinely mid-flight
            router.step()
        d0 = cl.handle("decode0")
        on_d0 = [rid for rid in rids
                 if router.outcome(rid) is None
                 and router._tracked[rid].worker == d0.rank]
        assert on_d0, "no in-flight rows on the migration source"
        # SIGKILL the source the instant the payload has left it — the
        # race the exactly-once ledger discipline exists for
        moved = router.migrate(on_d0, "decode0", "decode1",
                               _on_extracted=lambda: cl.kill("decode0"))
        assert moved == on_d0, (moved, on_d0)
        # wait for the FRONTEND OBSERVER's TTL to expire the corpse (a
        # fixed sleep races the observer clock: the elastic sweep may
        # first notice the final beat well after the kill)
        deadline = time.monotonic() + 30.0
        while "decode0" in set(router.elastic.members):
            assert time.monotonic() < deadline, \
                "TTL never expired the SIGKILLed source"
            time.sleep(0.1)
        router.step()                        # the sweep declares it dead
        router.drain()
        m = router.metrics()
    for i, rid in enumerate(rids):
        out = router.outcome(rid)
        assert out is not None and not isinstance(out, BaseException), \
            f"request {i} lost in the migration handoff: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged after the mid-handoff kill"
    assert m["states"]["decode0"] == "dead", m
    assert m["migrations"] == len(on_d0), m
    # the destination won: the source's death found NOTHING to requeue
    assert m["requeued"] == 0, \
        f"migrated rows were double-requeued off the corpse: {m}"
    return (f"source SIGKILLed mid-handoff, destination won "
            f"({len(on_d0)} rows), 0 requeues, all bit-exact")


def drill_rolling_restart_under_load(tmp):
    import numpy as np
    from paddle_tpu.serving import launch_cluster
    model, reqs, solo = _cluster_workload(n=4, seed=11)
    with launch_cluster(model, os.path.join(tmp, "rolling_cluster"),
                        prefill=0, decode=2, max_len=48,
                        engine_kw={"num_slots": 4, "chunk_size": 4},
                        heartbeat_s=0.3, ttl_s=6.0,
                        rpc_timeout_s=60.0) as cl:
        router = cl.router
        rids = [router.submit(p, n) for p, n in reqs]
        for _ in range(2):                   # rows genuinely mid-flight
            router.step()
        assert router.in_flight() >= 1, "workload drained too early"
        report = router.rolling_restart()
        router.drain()
        m = router.metrics()
    assert len(report["restarted"]) == 2, report
    for i, rid in enumerate(rids):
        out = router.outcome(rid)
        assert out is not None and not isinstance(out, BaseException), \
            f"request {i} lost across the rolling restart: {out!r}"
        assert np.array_equal(np.asarray(out), solo[i]), \
            f"request {i} diverged across the rolling restart"
    assert m["rolling_restarts"] == 2, m
    assert m["worker_deaths"] == 0, \
        f"a rolling restart leg was counted as a death: {m}"
    assert m["migrations"] >= 1, \
        f"the restart never live-migrated a row: {m}"
    return (f"both workers restarted under load ({m['migrations']} "
            f"rows migrated, 0 deaths), all bit-exact")


def drill_frontend_kill_mid_serve(tmp):
    from paddle_tpu.serving.cluster.frontend_proc import \
        run_frontend_failover_drill
    model, _, _ = _cluster_workload(n=1)
    base = run_frontend_failover_drill(
        model, os.path.join(tmp, "ffo_base"), kill=False)
    killed = run_frontend_failover_drill(
        model, os.path.join(tmp, "ffo_kill"), kill=True)
    ready = killed["ready"]
    assert ready["occupied"] >= 2 and ready["queued"] >= 2, \
        f"the kill window had too little in flight: {ready}"
    assert killed["zombie_error"] == "StaleEpochError", \
        f"zombie frontend not fenced typed: {killed['zombie_error']}"
    rep = killed["recovery"]
    total = (rep["finished_in_wal"] + rep["finished_in_gap"]
             + rep["resumed"] + rep["replayed"])
    assert total == len(base["outcomes"]), \
        f"recovery accounting lost requests: {rep}"
    for tag, out in base["outcomes"].items():
        assert killed["outcomes"][tag] == out, \
            f"{tag} diverged across the frontend failover"
    assert not any("unresolved" in o
                   for o in killed["outcomes"].values())
    return (f"frontend SIGKILLed (epoch {ready['epoch']} -> "
            f"{killed['epoch']}): {rep['resumed']} resumed in place, "
            f"{rep['replayed']} replayed, zombie fenced typed, all "
            f"{len(base['outcomes'])} bit-exact")


def drill_rpc_partition(tmp):
    import numpy as np
    from paddle_tpu.runtime.resilience import (ReplicaDeadError,
                                               fault_injector)
    from paddle_tpu.serving import launch_cluster
    model, reqs, solo = _cluster_workload(n=4, seed=12)
    # rpc_timeout_s starts LONG (the first step compiles the worker's
    # decode programs) and tightens only once the fleet is warm — a
    # dropped message then reads as a dead socket in ~3s, not 60
    with launch_cluster(model, os.path.join(tmp, "partition_cluster"),
                        prefill=0, decode=2, max_len=48,
                        engine_kw={"num_slots": 2, "chunk_size": 4},
                        heartbeat_s=0.3, ttl_s=30.0,
                        rpc_timeout_s=60.0) as cl:
        router = cl.router
        rids = [router.submit(p, n) for p, n in reqs]
        router.step()                        # warmup: compiles land
        router.rpc_timeout_s = 3.0
        victim = next(h for h in router.workers
                      if len(router._by_engine[h.rank]) >= 1)
        fault_injector.configure([
            {"kind": "rpc_partition", "src": "0",
             "dst": str(victim.rank)}])
        try:
            router.drain(max_steps=300)
            dropped = sum(1 for e in fault_injector.fired
                          if e.fault == "rpc_partition")
        finally:
            fault_injector.clear()
        m = router.metrics()
        assert m["worker_deaths"] == 1 and m["requeued"] >= 1, m
        for rid, want in zip(rids, solo):
            got = router.result(rid)       # raises on a lost request
            assert np.array_equal(np.asarray(got), want), \
                f"request {rid} diverged after the partition requeue"
        # sustained partition of the WHOLE pool: typed shed, no hang
        survivor = next(h for h in router.workers
                        if h.state == "healthy")
        rid2 = router.submit(reqs[0][0], 6)
        fault_injector.configure([
            {"kind": "rpc_partition", "src": "0",
             "dst": str(survivor.rank)}])
        try:
            router.drain(max_steps=300)
        finally:
            fault_injector.clear()
        try:
            router.result(rid2)
            raise AssertionError(
                "request under a total partition resolved silently")
        except ReplicaDeadError:
            pass
        try:
            router.submit(reqs[1][0], 6)
            raise AssertionError(
                "submit with no routable pool did not refuse typed")
        except ReplicaDeadError:
            pass
    return (f"asymmetric partition dropped {dropped} messages, victim "
            f"dead, {m['requeued']} requeued bit-exact; total "
            f"partition shed typed")


def main():
    import tempfile

    from paddle_tpu.flags import flags
    from paddle_tpu.runtime.resilience import fault_injector
    flags.set("resilience_backoff_s", 0.0)   # drills need no real sleeps
    drills = [
        ("transient_dispatch", drill_transient_dispatch, False),
        ("spec_verify_dispatch", drill_spec_verify_dispatch, False),
        ("torn_checkpoint", drill_torn_checkpoint, True),
        ("corrupt_bundle", drill_corrupt_bundle, True),
        ("dead_elastic", drill_dead_elastic, False),
        ("replica_kill", drill_replica_kill, False),
        ("hung_replica", drill_hung_replica, False),
        ("snapshot_torn_write", drill_snapshot_torn_write, True),
        ("worker_process_kill", drill_worker_process_kill, True),
        ("frontend_rpc_timeout", drill_frontend_rpc_timeout, True),
        ("migrate_mid_handoff_kill", drill_migrate_mid_handoff_kill,
         True),
        ("rolling_restart_under_load", drill_rolling_restart_under_load,
         True),
        ("frontend_kill_mid_serve", drill_frontend_kill_mid_serve,
         True),
        ("rpc_partition", drill_rpc_partition, True),
    ]
    results = {}
    ok = True
    with tempfile.TemporaryDirectory(prefix="fault_matrix_") as tmp:
        for name, fn, needs_tmp in drills:
            fault_injector.clear()
            t0 = time.monotonic()
            try:
                detail = fn(tmp) if needs_tmp else fn()
                results[name] = {"status": "pass", "detail": detail}
            except Exception as e:
                ok = False
                traceback.print_exc(file=sys.stderr)
                results[name] = {"status": "fail",
                                 "detail": f"{type(e).__name__}: "
                                           f"{str(e)[:200]}"}
            finally:
                fault_injector.clear()
            r = results[name]
            print(f"fault[{name}]: {r['status']} "
                  f"({time.monotonic() - t0:.1f}s) {r['detail']}",
                  file=sys.stderr)
    print(json.dumps({"metric": "fault_matrix", "ok": ok,
                      "classes": {k: v["status"]
                                  for k, v in results.items()}}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
