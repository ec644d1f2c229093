"""Continuous-batching serving engine over chunked resumable fused decode.

``ServingEngine`` drives the Orca-style loop: admit queued requests into
freed slots (one length-bucketed admission-prefill dispatch each, the
row state scattered into the batch carry), run ONE ``decode_chunk``
dispatch for T tokens across all slots, harvest finished rows on the
host, repeat. The decode stays a single device program per chunk — the
TPU requirement (Pope et al.) — while slots turn over independently, so
under mixed-length traffic the batch stays full instead of idling on
rows that already hit EOS.

Dispatch accounting is part of the contract (asserted by tests and
``bench.py --serve``): one admission prefill per admitted request plus
one chunk dispatch per engine step that had live rows — nothing hidden.
Admission scatters and row retirement are plain array updates outside
the counted dispatch sites.

Prefix caching (serving/prefix_cache.py, ``prefix_cache=`` /
``FLAGS_serving_prefix_cache_bytes``): admission consults a
content-hashed, ref-counted KV slab pool. A FULL-prefix hit admits via
the row-scatter alone — zero prefill dispatches — a PARTIAL hit
prefills only the uncached suffix (``admit_prefill``'s per-row
``pos0``), and a miss populates the pool on the way through; all three
paths are bit-exact with cold admission. ``batch_admission=`` folds
same-bucket waiting requests into one batched prefill dispatch
(``admission.dispatches_saved``). Both are off by default, keeping the
one-prefill-per-request accounting above exact.

Two backends serve the same scheduler:

- ``LlamaDecoder`` (in-process): jitted ``_ring_admit_prefill`` /
  ``_admit_prefill`` and ``_ring_chunk_decode`` entries;
- ``AotPredictor`` over a bundle exported with ``chunk_sizes=``:
  ``admit_prefill_s{S}.aot`` / ``decode_chunk_b{B}_t{T}.aot`` StableHLO
  entries — zero model Python at serve time (``decode_mode.chunked``).

Resilience: every dispatch retries transients (``resilient_call``
inside the backend's counted entries); a chunk that still fails steps
down to the per-token rung (T single-step dispatches on the SAME carry
— no in-flight request is dropped, since a failed dispatch never
consumed the state) with a typed ``DegradationEvent``, and the events
land on each affected request's result record.

Mesh serving (inference/sharding.py): a decoder built with ``mesh=`` —
or a bundle exported from one — serves TENSOR-PARALLEL over the ``tp``
axis with the batch (the slot table) on ``dp``. The ``DecodeState``
carry stays sharded on device across chunks AND across admission (the
row-scatter runs under the same NamedShardings), the per-token
degradation rung re-enters the same sharded carry, and ``status()``
reports the live topology + carry placements. ``mesh=`` on the engine
is a cross-check only: it must match the backend's, typed
``MeshMismatchError`` otherwise.

Deadlines + load shedding (``submit(deadline_s=)``): an expired budget
is refused typed (``DeadlineExceededError``) BEFORE any prefill, a
queue whose estimated delay already blows the budget sheds the submit
(backpressure), a queued request that expires while waiting is shed at
admission, and an in-flight row past its deadline is frozen like EOS at
the next chunk boundary and returned partial, flagged
``deadline_expired`` — the accepted-work contract is "tokens or a typed
error", never a silent drop and never a zombie burning slot-steps.

Crash recovery: ``snapshot(dir)`` serializes the carry (quantized
``{"q","s"}`` leaves and mesh shardings included) plus the slot/queue
bookkeeping under an atomic sha256-manifest write; ``restore(dir)`` on
a fresh same-shape engine verifies the manifest (typed
``CorruptCheckpointError`` on a torn/flipped file) and resumes with
bit-exact greedy continuation. ``snapshot_every_chunks=`` snapshots on
a chunk-boundary cadence and ``drain(deadline_s=)`` snapshots instead
of discarding — the graceful-drain story. ``replica_tag=`` names this
engine as one replica of a ``serving.router.ReplicaSet``: per-replica
fault-injection sites (``serving.<tag>.chunk``/``.step``) let a drill
kill ONE replica while its peers keep serving.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import logging
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import paddle_tpu.obs as obs
from paddle_tpu.obs.metrics import MetricsRegistry
from paddle_tpu.serving.scheduler import Request, Scheduler

__all__ = ["ServingEngine"]


def _admit_row(logits, kc, vc, pos, keys, done, eos, temp, aidx,
               logits1, kc1, vc1, slot, src, pos1, key1, eos1, temp1,
               aidx1):
    """Scatter one freshly prefilled request's row state into the batch
    carry at ``slot``. ``slot`` and ``src`` are traced scalars — one
    compiled program serves every slot index and every source row
    (``src`` picks the row out of ``logits1``/``kc1``/``vc1``, which may
    be a batched admission-prefill output or a batch-1 prefix-cache
    slab). A slab's cache buffers may be SHORTER than the carry on the
    length axis (length-bucketed slab pool): the update writes rows
    ``[0, bucket)`` and the stale tail past them stays causally masked
    until decode overwrites it — the padded-admission discipline. One
    fused update program instead of eight eager scatters; NOT a counted
    dispatch site (the serving dispatch contract counts prefills and
    chunks only)."""
    def put_cache(b, r):
        # batch axis: 0 for a layer's (B, ...) buffer; 1 for the one
        # (L, B, ...) array a bundle exported before the per-layer carry
        # still serves with — both are ndim-4 offsets from the row layout
        ax = b.ndim - 4
        r1 = jax.lax.dynamic_slice_in_dim(r, src, 1, axis=ax)
        starts = tuple(slot if i == ax else 0 for i in range(b.ndim))
        return jax.lax.dynamic_update_slice(b, r1.astype(b.dtype), starts)

    kc = jax.tree_util.tree_map(put_cache, kc, kc1)
    vc = jax.tree_util.tree_map(put_cache, vc, vc1)
    logits = logits.at[slot].set(
        jax.lax.dynamic_index_in_dim(logits1, src, axis=0,
                                     keepdims=False).astype(logits.dtype))
    pos = pos.at[slot].set(pos1)
    keys = keys.at[slot].set(key1)
    done = done.at[slot].set(False)
    eos = eos.at[slot].set(eos1)
    temp = temp.at[slot].set(temp1)
    if aidx is not None:
        aidx = aidx.at[slot].set(aidx1)
    return logits, kc, vc, pos, keys, done, eos, temp, aidx


_admit_row_jit = jax.jit(_admit_row)


def _as_sharding(mesh):
    from paddle_tpu.inference.sharding import DecodeSharding
    return mesh if isinstance(mesh, DecodeSharding) else DecodeSharding(mesh)


def _make_admit_fn(sharding, head_major):
    """The admission scatter for one engine. Off-mesh: the shared module
    jit. On a mesh: a jit that pins every output to the carry's
    NamedShardings — the row-scatter runs UNDER the same placements as
    the chunk program (the replicated batch-1 row state lands in the
    dp/tp-sharded carry on device; no gather, no placement decay)."""
    if sharding is None:
        return _admit_row_jit

    @jax.jit
    def admit(*args):
        logits, kc, vc, pos, keys, done, eos, temp, aidx = \
            _admit_row(*args)
        return sharding.constrain_carry(
            head_major, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
            done=done, eos=eos, temp=temp, adapter_idx=aidx)

    return admit


def _check_quant_ask(quant, have, what: str) -> None:
    """Typed quant-recipe cross-check: an engine/caller that asks for a
    dtype recipe must get exactly that recipe from its backend — an
    unquantized backend refuses a quantized ask, and vice versa. A
    ``None`` ask means "serve whatever the backend has" (back-compat)."""
    if quant is None:
        return
    from paddle_tpu.quantization.kv_cache import (QuantMismatchError,
                                                  canonical_quant)
    want = canonical_quant(quant)
    if want != have:
        raise QuantMismatchError(
            f"{what} serves quant recipe {have or 'none'!r} but the "
            f"engine asked for {want or 'none'!r}; rebuild the backend "
            f"with the matching quant= (or drop the ask)")


class _DecoderBackend:
    """In-process backend: the jitted chunk/admission entries of a
    ``LlamaDecoder``. The only backend with a device admission ring
    (``has_ring``) and speculative chunk entries (``draft_model=``)."""

    has_ring = True

    def __init__(self, dec, num_slots, chunk_size, do_sample, top_k, top_p,
                 mesh=None, quant=None, draft_model=None,
                 num_speculative_tokens=None, draft_quant=None,
                 adapter_store=None):
        from paddle_tpu.inference.sharding import MeshMismatchError
        _check_quant_ask(quant, getattr(dec, "quant", None),
                         "this LlamaDecoder")
        self.dec = dec
        self.lora = adapter_store
        self.lora_version = -1
        self.quant = getattr(dec, "quant", None)
        self.num_slots = int(num_slots)
        self.max_len = dec.max_len
        self.prompt_buckets = None          # any pow2 bucket compiles
        self.sharding = dec.sharding        # the decoder's mesh governs
        self.head_major = getattr(dec, "_head_major", False)
        # a model with windowed layers keeps those layers' keys in rolling
        # buffers: what addresses cache rows by position refuses it
        self.has_windows = dec.cfg.has_windows
        # an EVA model's cache layer is a window leaf and a summary leaf:
        # the window's rows and the positions a summary stands for
        self.eva = ((dec.cfg.cache_len(0, dec.max_len), dec.cfg.chunk_size)
                    if dec.cfg.eva else None)
        # a latent-attention model's cache layers (models/axk1.py): each
        # holds a compressed latent and a rope key, no keys and values
        # per head
        self.latent_layers = (dec.cfg.num_cache_layers if dec.cfg.latent
                              else 0)
        self.moe_counts = []    # state.moe of every chunk dispatch since
        #                         the engine last took them (harvest)
        # routed layers whose feed-forward the chunk program runs through
        # the grouped-FFN kernel (a trace-time fact: ops/moe.py)
        self.moe_ffn_kernel_layers = dec.moe_ffn_kernel_layers(
            self.num_slots)
        if mesh is not None:
            want = _as_sharding(mesh)
            if self.sharding is None:
                raise MeshMismatchError(
                    f"engine asked for mesh {want.axes} but the decoder "
                    f"was built without one; pass mesh= to LlamaDecoder")
            if not self.sharding.same_topology(want):
                raise MeshMismatchError(
                    f"engine mesh {want.axes} does not match the "
                    f"decoder's {self.sharding.axes}")
        if adapter_store is not None:
            self.refresh_adapters()
        self.spec_eng = None
        self.K = 0
        if draft_model is not None:
            from paddle_tpu.flags import flags
            K = int(num_speculative_tokens
                    if num_speculative_tokens is not None
                    else flags.decode_speculative_tokens)
            if K < 1:
                raise ValueError(
                    f"num_speculative_tokens must be >= 1, got {K}")
            self.spec_eng = dec._spec_engine(draft_model, draft_quant)
            self.K = K
        elif num_speculative_tokens is not None:
            raise ValueError("num_speculative_tokens requires a "
                             "draft_model")
        elif draft_quant is not None:
            raise ValueError("draft_quant requires a draft_model")
        self._kw = dict(
            do_sample=bool(do_sample),
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p))
        self._ring_logits = None
        self._empty1 = {}       # see _prefill_cache

    def _prefill_cache(self, N: int, draft: bool = False):
        """The empty caches an admission prefill of ``N`` rows starts
        from. An admission is one row unless ``batch_admission`` groups
        several, and no prefill program donates these inputs (the ring
        prefill donates the ring it stages into, never the pair it
        prefills from): the batch-1 pair is built once and shared by
        every admission (built afresh, 2 x layers eager ``jnp.zeros``
        held the v5e's host 15 ms an admission with the device idle:
        PERF.md section 6, PR 27)."""
        cfg = self.spec_eng["cfg"] if draft else None
        if N != 1:
            return self.dec._empty_cache(N, cfg)
        if draft not in self._empty1:
            self._empty1[draft] = self.dec._empty_cache(1, cfg)
        return self._empty1[draft]

    def refresh_adapters(self) -> bool:
        """(Re)merge the adapter store's stacked ``lora.*`` arrays into
        the decoder params. Shapes validate against the live param dict
        (the int8 base keeps its matrix geometry in the ``:int8``
        buffer). Returns True when device stacks actually moved. The
        param-dict TREEDEF changes the first time (new leaves), which
        retriggers the chunk traces — exactly the versioned-weights
        staging discipline: a swap is a new program-visible params
        value, never an in-place mutation under a running trace."""
        import jax.numpy as jnp
        store = self.lora
        if store is None or store.version == self.lora_version:
            return False
        p = self.dec.params
        shapes = {}
        for pn in store.param_names():
            w = p.get(pn)
            if w is None:
                w = p.get(pn + ":int8")
            if w is None:
                raise ValueError(
                    f"adapter store targets decoder param {pn!r} which "
                    f"this model does not have")
            shapes[pn] = tuple(int(s) for s in w.shape[-2:])
        stacks = store.stacks(param_shapes=shapes)
        dev = {k: jnp.asarray(v) for k, v in stacks.items()}
        if self.sharding is not None:
            from paddle_tpu.inference.sharding import DEFAULT_DECODE_RULES
            from paddle_tpu.parallel.placements import \
                match_partition_rules
            specs = match_partition_rules(DEFAULT_DECODE_RULES, dev)
            dev = {k: self.sharding.put(v, specs[k])
                   for k, v in dev.items()}
        self.dec.params.update(dev)
        self.lora_version = store.version
        return True

    def event_count(self) -> int:
        return len(self.dec._events)

    def events_since(self, n: int) -> list:
        return list(self.dec._events[n:])

    def new_state(self):
        import jax.numpy as jnp

        from paddle_tpu.inference.generate import DecodeState
        B = self.num_slots
        kc, vc = self.dec._empty_cache(B)   # born sharded under a mesh
        kw = {}
        if self.spec_eng is not None:
            # speculative serving carry: empty draft caches (admission
            # ring-prefills each row's), the pending-token sentinel and
            # zeroed per-row cumulative acceptance stats
            dkc, dvc = self.dec._empty_cache(B, self.spec_eng["cfg"])
            kw = dict(dkc=dkc, dvc=dvc,
                      tok=jnp.full((B,), -1, jnp.int32),
                      spec_rounds=jnp.zeros((B,), jnp.int32),
                      spec_accepted=jnp.zeros((B,), jnp.int32),
                      nv=jnp.zeros((B,), jnp.int32),
                      spec_on=jnp.ones((B,), jnp.bool_),
                      spec={"ekey": self.spec_eng["ekey"], "K": self.K})
        if self.lora is not None:
            kw["adapter_idx"] = jnp.zeros((B,), jnp.int32)
        st = DecodeState(
            logits=jnp.zeros((B, self.dec.cfg.vocab_size), jnp.float32),
            kc=kc, vc=vc,
            pos=jnp.zeros((B,), jnp.int32),
            keys=jnp.zeros((B, 2), jnp.uint32),
            done=jnp.ones((B,), jnp.bool_),    # every slot starts free
            eos=jnp.full((B,), -1, jnp.int32),
            temp=jnp.ones((B,), jnp.float32), **kw)
        if self.sharding is not None:
            st = self.sharding.put_state(st, self.head_major)
        return st

    # -- device admission ring ---------------------------------------------
    def ring_init(self, R: int) -> None:
        """Allocate the R-row device staging buffers the ring admission
        prefill scatters into (plus the draft-cache ring under
        speculation). Born under the carry's shardings on a mesh."""
        import jax.numpy as jnp
        self._ring_logits = jnp.zeros((R, self.dec.cfg.vocab_size),
                                      jnp.float32)
        if self.sharding is not None:
            # born where the admission prefill pins its output: an
            # uncommitted buffer would give the first-warmed bucket's
            # program another input sharding than every later call has,
            # and that bucket would compile again mid-serving
            self._ring_logits = self.sharding.put_state_field(
                "logits", self._ring_logits, self.head_major)
        self._ring_kc, self._ring_vc = self.dec._empty_cache(R)
        self._ring_dkc = self._ring_dvc = None
        if self.spec_eng is not None:
            self._ring_dkc, self._ring_dvc = self.dec._empty_cache(
                R, self.spec_eng["cfg"])

    def ring_admit(self, ids, true_len, pos0, ring_idx, aidx=None):
        """ONE counted admission-prefill dispatch whose results stage
        straight into device ring rows ``ring_idx`` — no host round-trip
        for the row state. ``aidx`` prefills each admitted row through
        its adapter's deltas (None = base for all rows). The program is
        given the ring (donated: the rows are written in place) and the
        three buffers are rebound from its result; a dispatch that
        fails after it has taken them leaves ``ring_consumed()`` true."""
        import jax.numpy as jnp
        ids = np.asarray(ids)
        kc, vc = self._prefill_cache(int(ids.shape[0]))
        self._ring_logits, self._ring_kc, self._ring_vc = \
            self.dec._ring_admit_prefill(
                self.dec.params, jnp.asarray(ids, jnp.int32), kc, vc,
                jnp.asarray(np.asarray(true_len), jnp.int32),
                jnp.asarray(np.asarray(pos0), jnp.int32),
                self._ring_logits, self._ring_kc, self._ring_vc,
                jnp.asarray(np.asarray(ring_idx), jnp.int32),
                None if aidx is None
                else jnp.asarray(np.asarray(aidx), jnp.int32))

    def ring_consumed(self) -> bool:
        """Whether a failed ring prefill took the donated ring with it
        (``ring_init`` builds a new, empty one)."""
        from paddle_tpu.inference.generate import _consumed
        return _consumed(self._ring_kc)

    def ring_admit_draft(self, ids, ring_idx):
        """The draft-model analog: one counted dispatch prefills the
        admitted prompts through the draft and stages the caches into
        the ring's draft buffers."""
        import jax.numpy as jnp
        eng = self.spec_eng
        ids = np.asarray(ids)
        dkc, dvc = self._prefill_cache(int(ids.shape[0]), draft=True)
        self._ring_dkc, self._ring_dvc = eng["ring_prefill"](
            eng["params"], jnp.asarray(ids, jnp.int32), dkc, dvc,
            self._ring_dkc, self._ring_dvc,
            jnp.asarray(np.asarray(ring_idx), jnp.int32))

    @staticmethod
    def _ring_dev(ring):
        import jax.numpy as jnp
        slot, pos, keys, eos, temp, aidx, son = ring
        return (jnp.asarray(slot, jnp.int32),
                jnp.asarray(pos, jnp.int32),
                jnp.asarray(keys, jnp.uint32),
                jnp.asarray(eos, jnp.int32),
                jnp.asarray(temp, jnp.float32),
                None if aidx is None else jnp.asarray(aidx, jnp.int32),
                None if son is None else jnp.asarray(son, jnp.bool_))

    def decode(self, st, steps, ring=None, rung="chunk"):
        """One dispatch of the decoder's chunk program over the serving
        carry: ``steps`` tokens for every slot, the host-side splice
        arrays ``ring`` (``ServingEngine._ring_args``; ``None`` on an
        engine that admits by host scatter) spliced in first.
        ``rung="step"`` dispatches the same program under the per-token
        rung's own fault site. ``st`` is consumed (its caches are
        donated); the ring's buffers are read and stay the backend's."""
        if ring is not None:
            slot, pos, keys, eos, temp, aidx, _son = self._ring_dev(ring)
            ring = (self._ring_logits, self._ring_kc, self._ring_vc,
                    slot, pos, keys, eos, temp, aidx)
        toks, st = self.dec._advance(
            self.dec._ring_chunk_decode if rung == "chunk"
            else self.dec._ring_chunk_step, st, steps, ring, **self._kw)
        if st.moe is not None:
            self.moe_counts.append(st.moe)
        return toks, st

    def decode_chunk_spec(self, st, chunk_size, ring, K=None):
        """One chunked-speculative dispatch over the serving carry;
        returns ``(buf (B, T+K), nv, new_state)`` — the overflow-buffer
        contract the engine's harvest slices. ``K=`` overrides the
        per-chunk draft depth (adaptive K clamps it from the live
        acceptance mean; each distinct K compiles once, like every
        other static)."""
        eng = self.spec_eng
        slot, pos, keys, eos, temp, aidx, son = self._ring_dev(ring)
        (buf, nv, logits, kc, vc, dkc, dvc, pos2, keys2, done, eos2,
         temp2, tok, sr, sa, aidx2, son2) = eng["chunk"](
            self.dec.params, eng["params"], st.logits, st.kc, st.vc,
            st.dkc, st.dvc, st.pos, st.keys, st.done, st.eos, st.temp,
            st.tok, st.spec_rounds, st.spec_accepted, st.adapter_idx,
            st.spec_on, self._ring_logits, self._ring_kc, self._ring_vc,
            self._ring_dkc, self._ring_dvc, slot, pos, keys, eos, temp,
            aidx, son, steps=int(chunk_size),
            K=self.K if K is None else int(K), **self._kw)
        return buf, nv, dataclasses.replace(
            st, logits=logits, kc=kc, vc=vc, dkc=dkc, dvc=dvc, pos=pos2,
            keys=keys2, done=done, eos=eos2, temp=temp2, tok=tok,
            spec_rounds=sr, spec_accepted=sa, nv=nv, adapter_idx=aidx2,
            spec_on=son2, steps_done=st.steps_done + int(chunk_size))

    def spec_demote(self, st):
        """Speculative -> chunked demotion: one counted masked forward
        commits each row's pending token, then the draft-side carry is
        dropped — the plain (ring) chunk program serves the state from
        here on."""
        eng = self.spec_eng
        logits, kc, vc, pos = eng["demote"](
            self.dec.params, st.logits, st.kc, st.vc, st.tok, st.pos,
            st.adapter_idx)
        return dataclasses.replace(
            st, logits=logits, kc=kc, vc=vc, pos=pos, dkc=None,
            dvc=None, tok=None, nv=None, spec=None, spec_on=None)

    # any admission batch size jits its own program; suffix prefills
    # (pos0 > 0) are native to the in-process entry
    admit_batch_any = True
    admit_pos0 = True

    def empty_cache(self, B: int):
        return self.dec._empty_cache(int(B))

    def admit_prefill(self, ids, true_len, pos0, kc=None, vc=None,
                      aidx=None):
        """One (possibly batched) admission-prefill dispatch: ``ids``
        (N, bucket) right-padded rows, per-row ``true_len``/``pos0``.
        ``kc``/``vc`` default to empty batch-N caches; the prefix-cache
        path passes caches preloaded with each row's slab. ``aidx``
        routes each row's prefill through its adapter's deltas."""
        import jax.numpy as jnp
        ids = np.asarray(ids)
        if kc is None:
            kc, vc = self._prefill_cache(int(ids.shape[0]))
        return self.dec._admit_prefill(
            self.dec.params, jnp.asarray(ids, jnp.int32), kc, vc,
            jnp.asarray(np.asarray(true_len), jnp.int32),
            jnp.asarray(np.asarray(pos0), jnp.int32),
            None if aidx is None
            else jnp.asarray(np.asarray(aidx), jnp.int32))

    def has_step_rung(self) -> bool:
        return True


class _BundleBackend:
    """AOT backend: the ``decode_chunk_b{B}_t{T}`` / ``admit_prefill_s{S}``
    StableHLO entries of a bundle exported with ``chunk_sizes=`` — the
    serving process runs no model Python (``decode_mode.chunked``)."""

    has_windows = False    # (an exported program is full attention)
    eva = None
    latent_layers = 0      # (a bundle of a latent model is refused)
    moe_counts = ()
    moe_ffn_kernel_layers = 0
    has_ring = False       # bundles carry no ring-staging entries: the
    #                        engine falls back to the host row-scatter
    spec_eng = None
    K = 0
    lora = None            # typed refusal in __init__: no adapter stacks

    def __init__(self, pred, num_slots, chunk_size, do_sample, top_k,
                 top_p, mesh=None, quant=None, draft_model=None,
                 num_speculative_tokens=None, draft_quant=None,
                 adapter_store=None):
        from paddle_tpu.inference.sharding import MeshMismatchError
        if draft_model is not None or num_speculative_tokens is not None \
                or draft_quant is not None:
            mode = (pred.meta.get("decode_mode") or {})
            ch0 = mode.get("chunked") or {}
            raise ValueError(
                f"speculative serving needs the in-process LlamaDecoder "
                f"backend: this bundle's chunked entries carry no "
                f"speculative chunk program (decode_mode.chunked."
                f"spec_chunk={bool(ch0.get('spec_chunk'))!r}); serve "
                f"draft_model= over a LlamaDecoder instead")
        if adapter_store is not None:
            raise ValueError(
                "LoRA adapter serving needs the in-process LlamaDecoder "
                "backend: this bundle's StableHLO entries were exported "
                "without the stacked lora.* params or the adapter_idx "
                "carry; serve adapter_store= over a LlamaDecoder instead")
        _check_quant_ask(quant, pred.quant_recipe, "this bundle")
        self.pred = pred
        self.quant = pred.quant_recipe
        self.num_slots = int(num_slots)
        meta = pred.meta
        mode = meta.get("decode_mode") or {}
        # the mesh contract travels in bundle.json: a bundle exported
        # under a mesh only serves that topology (its StableHLO entries
        # are partitioned programs), and an engine that asks for a mesh
        # refuses a single-device bundle — typed, at load, never a
        # mid-serve device-count crash
        self.sharding = pred._sharding      # from decode_mode.mesh
        self.head_major = pred._head_major()
        if mesh is not None:
            want = _as_sharding(mesh)
            if self.sharding is None:
                raise MeshMismatchError(
                    f"engine asked for mesh {want.axes} but this bundle "
                    f"was exported without one; re-export from a "
                    f"mesh-built LlamaDecoder")
            if not self.sharding.same_topology(want):
                raise MeshMismatchError(
                    f"engine mesh {want.axes} does not match the "
                    f"bundle's recorded {self.sharding.axes}")
        ch = mode.get("chunked")
        if not ch:
            raise ValueError(
                "this bundle has no chunked decode entries; re-export it "
                "with export_decoder_bundle(..., chunk_sizes=[...]) to "
                "serve continuous batching")
        for name, want in (("do_sample", bool(do_sample)),
                           ("top_k", top_k), ("top_p", top_p)):
            baked = mode.get(name)
            if name == "do_sample":
                baked = bool(baked)
            if baked != want:
                raise ValueError(
                    f"bundle chunked entries were exported with "
                    f"{name}={baked!r}; the engine asked for {want!r}")
        self.max_len = meta["max_len"]
        by_chunk = {b["chunk"]: b["file"] for b in meta["chunk_buckets"]
                    if b["batch"] == self.num_slots}
        if int(chunk_size) not in by_chunk:
            have = [(b["batch"], b["chunk"])
                    for b in meta["chunk_buckets"]]
            raise ValueError(
                f"no chunked decode bucket for batch={self.num_slots}, "
                f"chunk={chunk_size}; exported (batch, chunk): {have}")
        self._chunk_file = by_chunk[int(chunk_size)]
        self._step_file = by_chunk.get(1)
        self._admit = {b["seq"]: b["file"]
                       for b in meta["admit_prefill_buckets"]}
        self.admit_pos0 = bool(ch.get("admit_pos0"))
        self.prompt_buckets = sorted(self._admit)
        self._logits_dtype = meta.get("logits_dtype", "float32")
        self._vocab = meta["vocab_size"]

    def event_count(self) -> int:
        return len(self.pred._events)

    def events_since(self, n: int) -> list:
        return list(self.pred._events[n:])

    def new_state(self):
        import jax.numpy as jnp

        from paddle_tpu.inference.generate import DecodeState
        B = self.num_slots
        kc, vc = self.pred._make_cache(B)   # sharded when meta says so
        st = DecodeState(
            logits=jnp.zeros((B, self._vocab),
                             jnp.dtype(self._logits_dtype)),
            kc=kc, vc=vc,
            pos=jnp.zeros((B,), jnp.int32),
            keys=jnp.zeros((B, 2), jnp.uint32),
            done=jnp.ones((B,), jnp.bool_),
            eos=jnp.full((B,), -1, jnp.int32),
            temp=jnp.ones((B,), jnp.float32))
        if self.sharding is not None:
            st = self.sharding.put_state(st, self.head_major)
        return st

    # bundle admit entries are fixed batch-1 StableHLO modules; suffix
    # prefills need the pos0-taking entries (decode_mode.chunked
    # admit_pos0 — absent on pre-prefix bundles, whose partial hits the
    # engine demotes to misses)
    admit_batch_any = False

    def empty_cache(self, B: int):
        return self.pred._make_cache(int(B))

    def admit_prefill(self, ids, true_len, pos0, kc=None, vc=None,
                      aidx=None):
        import jax.numpy as jnp
        if aidx is not None:
            # unreachable today: __init__ refuses adapter_store=, so the
            # engine never computes row indices for a bundle backend
            raise ValueError(
                "bundle admit entries carry no adapter_idx input; serve "
                "adapter_store= over a LlamaDecoder instead")
        ids = np.asarray(ids)
        if ids.shape[0] != 1:
            raise ValueError(
                f"bundle admit entries serve batch 1, got {ids.shape[0]}")
        S = int(ids.shape[1])
        if S not in self._admit:
            raise ValueError(f"no admit_prefill bucket for prompt bucket "
                             f"{S}; exported: {self.prompt_buckets}")
        if kc is None:
            kc, vc = self.pred._make_cache(1)
        ids_d = jnp.asarray(ids, jnp.int32)
        tl = jnp.asarray(np.asarray(true_len), jnp.int32)
        p0 = jnp.asarray(np.asarray(pos0), jnp.int32)
        if not self.admit_pos0:
            if int(np.asarray(pos0)[0]) != 0:
                raise ValueError(
                    "this bundle's admit entries predate the prefix "
                    "cache (no pos0 input); re-export it for suffix "
                    "prefills")
            # legacy entry signature: scalar true_len, no pos0
            tl = jnp.asarray(int(np.asarray(true_len)[0]), jnp.int32)
            if self.sharding is not None:
                ids_d = self.sharding.put(ids_d, ())
                tl = self.sharding.put(tl, ())
            return self.pred._run_entry(
                self._admit[S], "bundle.admit_prefill", ids_d, kc, vc, tl)
        if self.sharding is not None:
            # partitioned admit entries take committed mesh arrays
            ids_d = self.sharding.put(ids_d, ())
            tl = self.sharding.put(tl, ())
            p0 = self.sharding.put(p0, ())
        return self.pred._run_entry(
            self._admit[S], "bundle.admit_prefill", ids_d, kc, vc, tl, p0)

    def decode(self, st, steps, ring=None, rung="chunk"):
        """One dispatch of the exported chunk entry (``rung="step"``: the
        T=1 entry, under its own fault site); ``steps`` was baked at
        export."""
        if ring is not None:
            raise ValueError(
                "bundle chunk entries carry no admission ring "
                "(decode_mode.chunked.admit_ring is false)")
        fname, site = ((self._chunk_file, "bundle.chunk")
                       if rung == "chunk"
                       else (self._step_file, "bundle.chunk_step"))
        toks, logits, kc, vc, pos, keys, done = self.pred._run_entry(
            fname, site, st.logits, st.kc, st.vc, st.pos, st.keys,
            st.done, st.eos, st.temp)
        return toks, dataclasses.replace(
            st, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
            done=done)

    def has_step_rung(self) -> bool:
        return self._step_file is not None


def derive_row_key(seed: int, request_id: int, tokens_emitted: int):
    """The request-keyed row RNG stream (``request_keyed_rng=True``):
    start from ``fold_in(PRNGKey(seed), request_id)`` and advance the
    key once per already-emitted token with the SAME rule the chunked
    scan body uses (``next = split(key)[0]``, the sampling sub being
    ``split(key)[1]``). An admission that replays ``tokens_emitted``
    teacher-forced tokens therefore resumes the exact key the
    undisturbed row would hold — sampled requeue/replay on a different
    engine or worker draws the identical continuation."""
    import jax.random as jrandom
    key = jrandom.split(
        jrandom.fold_in(jrandom.PRNGKey(int(seed)), int(request_id)),
        1)[0]
    for _ in range(int(tokens_emitted)):
        key = jrandom.split(key)[0]
    return key


def _make_backend(backend, num_slots, chunk_size, do_sample, top_k, top_p,
                  mesh=None, quant=None, draft_model=None,
                  num_speculative_tokens=None, draft_quant=None,
                  adapter_store=None):
    from paddle_tpu.inference.bundle import AotPredictor
    from paddle_tpu.inference.generate import LlamaDecoder
    kw = dict(mesh=mesh, quant=quant, draft_model=draft_model,
              num_speculative_tokens=num_speculative_tokens,
              draft_quant=draft_quant, adapter_store=adapter_store)
    if isinstance(backend, LlamaDecoder):
        return _DecoderBackend(backend, num_slots, chunk_size, do_sample,
                               top_k, top_p, **kw)
    if isinstance(backend, AotPredictor):
        return _BundleBackend(backend, num_slots, chunk_size, do_sample,
                              top_k, top_p, **kw)
    raise TypeError(
        f"backend must be a LlamaDecoder or an AotPredictor, "
        f"got {type(backend).__name__}")


class ServingEngine:
    """Slot-admission continuous-batching engine.

    ``submit`` queues a request and returns its id; ``step`` runs one
    admit-dispatch-harvest iteration and returns the requests it
    finished; ``drain`` steps until queue and slots are empty. Results
    are ``GenerateResult`` arrays (prompt + generated tokens, trimmed at
    the request's eos / budget) whose ``.resilience`` record carries the
    ladder level, retries, degradations and serving stats (queue delay,
    chunks spanned, slot index) of that request's lifetime.

    Greedy outputs are bit-exact with a solo ``LlamaDecoder.generate``
    of the same request — admission, chunk slicing and batch neighbours
    cannot change a request's tokens. Sampled outputs are bit-exact
    across engine configurations (per-row key streams keyed only by the
    request's ``seed``), and distribution-preserving vs the fused path.

    ``do_sample`` / ``top_k`` / ``top_p`` are engine-wide statics (they
    change the compiled chunk program); eos id, temperature and seed are
    per-request runtime inputs.

    ``slo_targets`` maps a latency class to its default SLO targets,
    e.g. ``{"interactive": {"ttft_s": 0.2, "latency_s": 2.0}}`` —
    per-request ``slo_ttft_s``/``slo_latency_s`` override them. Every
    finished request observes the per-class TTFT (admission -> first
    token) and TPOT (inter-token) histograms; a request that misses a
    target bumps the per-class ``serving.slo.<class>.*_violations``
    counters (the control signal SLO-aware admission will read).
    """

    def __init__(self, backend, num_slots: int = 4, chunk_size: int = 8,
                 do_sample: bool = False, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, policy: str = "fifo",
                 prompt_buckets: Optional[Sequence[int]] = None,
                 slo_targets: Optional[Dict[str, Dict[str, float]]]
                 = None, mesh=None, prefix_cache=None,
                 prefix_cache_bytes: Optional[int] = None,
                 prefix_block_tokens: Optional[int] = None,
                 batch_admission: bool = False, quant: Optional[str]
                 = None, cache_aware_admission: Optional[bool] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every_chunks: int = 0,
                 replica_tag: Optional[str] = None,
                 request_keyed_rng: bool = False,
                 draft_model=None,
                 num_speculative_tokens: Optional[int] = None,
                 draft_quant: Optional[str] = None,
                 ring_slots: Optional[int] = None,
                 adapter_store=None,
                 adaptive_k: bool = False):
        """``prefix_cache``: ``None`` reads the
        ``FLAGS_serving_prefix_cache_bytes`` /
        ``PADDLE_TPU_PREFIX_CACHE_BYTES`` budget (0 = disabled, the
        default); ``True`` enables it (budget from
        ``prefix_cache_bytes``, the flags, or effectively unlimited);
        ``False`` disables; a ``PrefixCache`` instance is served
        directly — shareable across same-topology engines, refused
        typed (``MeshMismatchError``) on a mesh mismatch.
        ``batch_admission``: admit several same-bucket waiting requests
        with ONE batched (suffix-)prefill dispatch instead of
        per-request batch-1 prefills (``admission.dispatches_saved`` in
        ``metrics()``); off by default — the classic one-prefill-per-
        request accounting stays exact.
        ``quant``: cross-check only — the backend must serve exactly
        this dtype recipe ('int8w'/'int8wk'/'none'); an unquantized
        backend refuses a quantized ask typed
        (``QuantMismatchError``) and vice versa. ``None`` = serve
        whatever the backend has.
        ``cache_aware_admission``: among same-priority queued requests,
        admit in an order that maximizes prefix-slab reuse (requests
        whose digest is already cached lead; same-digest requests admit
        together; FIFO within a digest group) — defaults to ON whenever
        the prefix cache is enabled; ``serving.admission.cache_reordered``
        in ``metrics()`` counts the queue jumps.
        ``snapshot_dir``/``snapshot_every_chunks``: write a resumable
        carry snapshot (:meth:`snapshot`) into ``snapshot_dir`` every N
        chunk dispatches (0 = never; the default) — the crash-recovery
        cadence. ``replica_tag``: names this engine as one replica of a
        router's ``ReplicaSet`` and arms the per-replica fault sites.
        ``request_keyed_rng``: derive each admitted row's RNG stream
        from ``(seed, request id, tokens already emitted)`` instead of
        the seed alone — a sampled request REQUEUED onto another
        engine/worker with its generated tokens replayed resumes the
        identical stream, so non-greedy requeue replay is bit-exact
        too. Off by default: the classic seed-only rule keeps
        engine-sampled outputs bit-exact with a solo
        ``generate(do_sample=True)`` of the same seed.
        ``draft_model``/``num_speculative_tokens``/``draft_quant``:
        SPECULATIVE serving (LlamaDecoder backend only) — every chunk
        dispatch runs draft/verify/accept rounds committing a per-row
        variable ``[chunk_size, chunk_size+K]`` tokens, the K-fold
        tokens-per-dispatch win of Leviathan et al. under continuous
        batching; greedy tokens stay bit-exact with the plain engine.
        ``ring_slots``: rows in the device admission ring (default
        ``num_slots``; LlamaDecoder backend only) — admissions stage
        prefill results device-side and the next chunk program splices
        them in, so steady state is exactly one dispatch per chunk;
        admissions beyond the ring's free rows re-queue at their tier's
        head (``serving.admission.ring_full``).
        ``adapter_store``: multi-tenant LoRA serving (LlamaDecoder
        backend only) — requests name a registered adapter and the
        chunk program gathers each row's stacked low-rank deltas inside
        the ONE fused dispatch (serving/lora); base rows ride along
        bit-exact. Hot-swapped revisions apply between chunks once no
        in-flight row pins the old one (``AdapterVersionError`` names
        the blocking rows otherwise).
        ``adaptive_k``: clamp each speculative chunk's draft depth K
        from the live cumulative acceptance mean (K stays in ``[1,
        num_speculative_tokens]``; each distinct K compiles once) — the
        verify-compute knob tracks the workload instead of the flag."""
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.num_slots = int(num_slots)
        self.chunk_size = int(chunk_size)
        self._b = _make_backend(backend, num_slots, chunk_size, do_sample,
                                top_k, top_p, mesh=mesh, quant=quant,
                                draft_model=draft_model,
                                num_speculative_tokens=num_speculative_tokens,
                                draft_quant=draft_quant,
                                adapter_store=adapter_store)
        self._spec_configured = self._b.spec_eng is not None
        self._spec_active = self._spec_configured
        if adaptive_k and not self._spec_configured:
            raise ValueError("adaptive_k requires a draft_model")
        self.adaptive_k = bool(adaptive_k)
        self._k_now = self._b.K
        self._accept_ewma: Optional[float] = None
        self.adapter_store = adapter_store
        # the revisions the DEVICE stacks actually serve (mirrors the
        # store at every applied swap; the skew window is the staged-
        # but-refused hot-swap)
        self._served_rev: Dict[str, int] = (
            {} if adapter_store is None
            else {n: adapter_store.revision(n)
                  for n in adapter_store.names()})
        if self._spec_configured and (snapshot_dir or snapshot_every_chunks):
            raise ValueError(
                "speculative serving does not snapshot yet: the carry's "
                "draft caches and pending-token fields are outside the "
                "snapshot payload — drop snapshot_dir/"
                "snapshot_every_chunks or serve without draft_model")
        # on a mesh the slot table maps onto the dp axis: contiguous
        # blocks of num_slots/dp rows are one data-parallel replica's
        # slots (jax shards a dim into contiguous blocks); the scheduler
        # carries the grouping for status/placement introspection
        srd = self._b.sharding
        dp = srd.dp_shards(self.num_slots) if srd is not None else 1
        self.scheduler = Scheduler(
            num_slots, policy=policy,
            prompt_buckets=prompt_buckets or self._b.prompt_buckets,
            dp_size=dp)
        self._admit_fn = _make_admit_fn(srd, self._b.head_major)
        self.request_keyed_rng = bool(request_keyed_rng)
        self.state = self._b.new_state()
        self._next_id = 0
        self._results: Dict[int, Any] = {}
        # content-hashed prefix cache (serving/prefix_cache.py): a full
        # hit admits via the row-scatter alone — zero prefill dispatches
        self.batch_admission = bool(batch_admission)
        self.prefix_cache = self._resolve_prefix_cache(
            prefix_cache, prefix_cache_bytes, prefix_block_tokens)
        if self._b.latent_layers and self.prefix_cache is not None:
            from paddle_tpu.inference.generate import LatentCacheError
            raise LatentCacheError(
                "the prefix cache keeps a slab of keys and values per head "
                "by position; a model with latent attention caches a "
                "latent and a rope key instead — serve it with "
                "prefix_cache=False")
        if self._b.has_windows and self.prefix_cache is not None:
            from paddle_tpu.inference.generate import WindowedModelError
            raise WindowedModelError(
                "the prefix cache loads a slab's rows at their positions "
                "and prefills the suffix from there; a model with "
                "windowed layers keeps a position at position % window "
                "and prefills from 0 only — serve it with "
                "prefix_cache=False")
        if self._spec_configured and self.prefix_cache is not None:
            raise ValueError(
                "speculative serving does not compose with the prefix "
                "cache yet: slab admission bypasses the ring's draft-"
                "cache staging — disable prefix_cache or drop "
                "draft_model")
        # device admission ring: staged admissions splice into the carry
        # inside the NEXT chunk dispatch (no host scatter, no extra
        # dispatch boundary). Ring-capable backends only; the prefix-
        # cache admission path needs the host scatter (slab loads), so
        # the cache keeps the legacy route.
        self._ring_slots = 0
        self._ring_meta: List[Optional[dict]] = []
        if self._b.has_ring and self.prefix_cache is None:
            R = int(ring_slots if ring_slots is not None else num_slots)
            if R < 1:
                raise ValueError(f"ring_slots must be >= 1, got {R}")
            self._ring_slots = R
            self._ring_meta = [None] * R
            self._b.ring_init(R)
        elif ring_slots is not None:
            raise ValueError(
                "ring_slots needs the device admission ring: an "
                "in-process LlamaDecoder backend without a prefix cache")
        elif self._spec_configured:
            raise ValueError(
                "speculative serving needs the device admission ring "
                "(in-process LlamaDecoder backend, no prefix cache)")
        self._last_nv: Optional[np.ndarray] = None
        self._slab_ops = None
        if self.prefix_cache is not None:
            from paddle_tpu.serving.prefix_cache import SlabOps
            # slabs live under the carry's NamedShardings; a shared
            # cache refuses a different topology typed, at bind time
            self.prefix_cache.bind_mesh(srd.axes if srd is not None
                                        else None)
            self._slab_ops = SlabOps(srd, self._b.head_major)
        # cache-aware admission ordering: on by default when the prefix
        # cache is (the scheduler's probe answers "is this digest a
        # guaranteed slab hit right now"); reordering is confined to a
        # priority tier and FIFO holds within a digest group
        self._cache_aware = (bool(cache_aware_admission)
                             if cache_aware_admission is not None
                             else self.prefix_cache is not None)
        if self._cache_aware and self.prefix_cache is not None:
            self.scheduler.cache_aware = True
            self.scheduler.cache_probe = self.prefix_cache.has_digest
        # the engine's own always-on metrics registry (paddle_tpu/obs):
        # replaces the ad-hoc counter ints / delay-and-occupancy lists of
        # round 9 — same bookkeeping cost, but one typed store feeding
        # metrics(), the Prometheus export and the bench obs block.
        # Timeline SPANS (per-request queued->admitted->finished) go to
        # the global tracer and stay obs-gated.
        self.registry = MetricsRegistry()
        r = self.registry
        self._c_prefill = r.counter(
            "serving.prefill_dispatches",
            "admission prefills (exactly one per admitted request)")
        self._c_chunk = r.counter(
            "serving.chunk_dispatches",
            "fused decode_chunk dispatches (one per step with live rows)")
        self._c_step = r.counter(
            "serving.step_dispatches",
            "per-token degradation-rung dispatches")
        self._c_degr = r.counter("serving.degradations",
                                 "chunk->per_token degradations")
        self._c_slot_steps = r.counter(
            "serving.slot_steps",
            "slot-steps run (ALL rows compute every chunk step — the "
            "honest useful-token-occupancy denominator)")
        self._c_done = r.counter("serving.requests_completed", "")
        self._h_qdelay = r.histogram(
            "serving.queue_delay_s", "submit -> admission wait")
        self._h_latency = r.histogram(
            "serving.request_latency_s", "submit -> finished")
        self._h_occ = r.histogram(
            "serving.occupancy", "occupied-slot fraction per chunk "
            "dispatch", buckets=[i / 8 for i in range(1, 9)])
        self._h_qdepth = r.histogram(
            "serving.queue_depth", "queued requests observed per step",
            buckets=[0, 1, 2, 4, 8, 16, 32, 64, 128])
        self._g_qdepth = r.gauge("serving.queue_depth_now", "")
        # SLO instruments: TTFT is admission -> the end of the first
        # chunk dispatch the request rode (its first tokens exist on the
        # host then); TPOT is (finish - first token) / (tokens - 1) per
        # request — chunked execution quantizes both to chunk boundaries
        self._h_ttft = r.histogram(
            "serving.ttft_s", "time to first token (admission -> first "
            "chunk completion)")
        self._h_tpot = r.histogram(
            "serving.tpot_s", "per-request mean inter-token time after "
            "the first token")
        self._h_ttft_submit = r.histogram(
            "serving.ttft_from_submit_s", "time to first token as the "
            "caller sees it (submit -> first chunk completion: the "
            "queue wait + serving.ttft_s, on one clock)")
        # the step measured from inside: its four phases tile step(), in
        # this order, each a profiler annotation serving.step.<phase> and
        # an always-on histogram of the phase's seconds per step.
        # admit_wait is no fifth phase: it is the part of admit that the
        # host spends blocked on a device read (the ring's row-key
        # readback, queued behind the prefill), one interval a readback
        self._h_phase = {
            ph: r.histogram(f"serving.step.phase_s.{ph}", what)
            for ph, what in (
                ("admit", "deadline sweep, admissions, prefill enqueue "
                          "and row-key readback, per step"),
                ("dispatch", "ring arguments and the chunk enqueue, up "
                             "to its return, per step"),
                ("wait", "host blocked on the chunk's tokens (device "
                         "time, not host gap), per step"),
                ("harvest", "post-chunk logits readback and finite "
                            "check, slot loop, callbacks, per step"),
                ("admit_wait", "inside admit: host blocked on the "
                               "row-key readback behind the prefill "
                               "(device time), per readback"))}
        self._open_phase = None
        # the device's timeline as this host knows it (obs.DeviceTimeline):
        # fed from the return of an enqueue to the end of the blocking
        # read that waits it out, starved between that read and the next
        # enqueue — by the phase of step() the host is in, or outside
        # step() with work unfinished — and no_work with none. The parts
        # tile wall time; a second or more in one interval is a stall
        self._c_timeline = {
            part: r.counter(name, what) for part, name, what in (
                ("fed.chunk", "serving.device.fed_s.chunk",
                 "seconds from the return of a chunk's enqueue to the "
                 "end of the blocking read of its tokens"),
                ("fed.prefill", "serving.device.fed_s.prefill",
                 "seconds from the return of an admission prefill's "
                 "enqueue to the end of the read that waits it out (the "
                 "row-key readback) or to the next enqueue"),
                ("starved.admit", "serving.device.starved_s.admit",
                 "seconds the device was known empty with the host in "
                 "step()'s admit phase"),
                ("starved.dispatch", "serving.device.starved_s.dispatch",
                 "seconds the device was known empty with the host in "
                 "step()'s dispatch phase"),
                ("starved.harvest", "serving.device.starved_s.harvest",
                 "seconds the device was known empty with the host in "
                 "step()'s harvest phase"),
                ("starved.outside", "serving.device.starved_s.outside",
                 "seconds the device was known empty between two step() "
                 "calls with work submitted and unfinished (the "
                 "caller's time)"),
                ("no_work", "serving.device.no_work_s",
                 "seconds the device was known empty with nothing "
                 "submitted that was unfinished"))}
        self._timeline = obs.DeviceTimeline(
            self._c_timeline, log=logging.getLogger("paddle_tpu.serving"))
        self._c_live_kv = r.counter(
            "serving.chunk.live_kv_positions",
            "sum over the occupied rows of the row's cache position at "
            "the chunk's start, per chunk dispatch (the KV a chunk's "
            "attention must at least read)")
        self._c_live_win = r.counter(
            "serving.chunk.live_window_positions",
            "sum over the occupied rows of min(the row's cache position, "
            "the rolling buffers' length) at the chunk's start, per chunk "
            "dispatch (what a windowed layer's attention reads of a row; "
            "0 for a model with none)")
        # a model whose cache layer is a window leaf beside a summary leaf
        # (models/evabyte.py): live_window_positions counts the window
        # leaf's live rows (kv_pos % window + 1), and these the rest
        self._c_live_sum = r.counter(
            "serving.chunk.live_summary_positions",
            "sum over the occupied rows of the summaries visible at the "
            "chunk's start ((kv_pos // window) x (window / chunk)), per "
            "chunk dispatch")
        # a latent model's decode attention (models/axk1.py) reads, a
        # step, each occupied row's live positions in every latent layer
        self._c_live_latent = r.counter(
            "serving.chunk.live_latent_positions",
            "latent positions the chunks' decode steps read: over the "
            "occupied rows at the chunk's start, the chunk's steps (a "
            "step at position n reads n + 1) and the latent cache layers "
            "(0 for a model without latent attention)")
        # bucket -> what its admission prefills needed (_count_prefill)
        self._prefill_need: Dict[int, Dict[str, int]] = {}
        self._c_eva_chunks = r.counter(
            "serving.eva.chunks_summarised",
            "chunks whose summary a decode step completed, over the "
            "occupied rows and the chunks' steps")
        self._c_eva_ends = r.counter(
            "serving.eva.window_ends",
            "window ends decode steps crossed (the window leaf reset, "
            "window / chunk more summaries visible), over the occupied "
            "rows and the chunks' steps")
        self._c_eva_prefill = r.counter(
            "serving.eva.prefill_windows",
            "aligned windows the admission prefills covered (a prompt of "
            "n positions: ceil(n / window))")
        # bucket -> what its prefills needed (_count_eva_prefill)
        self._eva_prefill: Dict[int, Dict[str, int]] = {}
        # what the routing did, from the vector the chunk program of a
        # model with routed feed-forwards returns beside its tokens
        self._c_moe_pairs = r.counter(
            "serving.moe.pairs_held",
            "token-expert pairs that landed on held experts, summed over "
            "the chunks' steps and routed layers")
        self._c_moe_touched = r.counter(
            "serving.moe.experts_touched",
            "held experts with at least one token, summed over the "
            "chunks' steps and routed layers (the expert weights a step "
            "has to read)")
        self._g_moe_load = r.gauge(
            "serving.moe.load_max",
            "the largest count one held expert took in one step of the "
            "last chunk")
        self._moe_ffn_kernel_layers = int(self._b.moe_ffn_kernel_layers)
        r.gauge("serving.moe.ffn_kernel_layers",
                "routed layers whose experts' feed-forward the chunk "
                "program runs through the grouped-FFN kernel (0 where "
                "XLA's ragged_dot pair runs)"
                ).set(self._moe_ffn_kernel_layers)
        # the cache as built, from the carry's own buffers: a looped
        # model holds its weight layers once per pass, a windowed layer
        # a rolling buffer of its window (shorter than max_len or not)
        kc = self.state.kc
        self._cache_layers = (len(kc) if isinstance(kc, tuple)
                              else int(kc.shape[0]))
        self._cache_bytes_per_position = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(
                (kc, self.state.vc))) // (self.num_slots * self._b.max_len)
        by_len: Dict[int, int] = {}     # buffer length -> K and V bytes
        for x in jax.tree_util.tree_leaves((kc, self.state.vc)):
            if x.ndim == 4:             # a layer's own (B, ...) buffer
                n = int(x.shape[2 if self._b.head_major else 1])
                by_len[n] = by_len.get(n, 0) + x.nbytes
        full = max(by_len, default=self._b.max_len)
        self._window_len = min(by_len, default=full)
        self._cache_bytes_full = by_len.get(full, 0) // (
            self.num_slots * full)
        self._cache_bytes_window = sum(
            b // (self.num_slots * n) for n, b in by_len.items() if n < full)
        self._cache_bytes_summary = 0
        self._leaf_kinds = 1 if self._b.eva is None else 2
        if self._b.eva is not None:
            # a window leaf (an even buffer of the carry) beside a summary
            # leaf (the odd one after it) a layer: by the leaf's place, not
            # by a length the two may share; a leaf's bytes a ROW, over
            # the layers
            def row_bytes(bufs):
                return sum(x.nbytes // (self.num_slots * int(x.shape[2]))
                           for x in bufs)
            self._window_len = self._b.eva[0]
            self._cache_bytes_full = 0
            self._cache_bytes_window = (row_bytes(kc[0::2])
                                        + row_bytes(self.state.vc[0::2]))
            self._cache_bytes_summary = (row_bytes(kc[1::2])
                                         + row_bytes(self.state.vc[1::2]))
            self._cache_layers //= 2
        r.gauge("serving.cache.leaf_kinds",
                "kinds of leaf a cache layer holds (1: keys and values by "
                "position; 2: a window of exact positions beside chunk "
                "summaries)").set(self._leaf_kinds)
        r.gauge("serving.cache.bytes_per_position.summary",
                "bytes of K and V one summary entry holds over the cache "
                "layers (0 for a model without summary leaves)"
                ).set(self._cache_bytes_summary)
        r.gauge("serving.cache.bytes_per_position.full",
                "bytes of K and V one position holds over the cache "
                "layers that keep all of max_len").set(self._cache_bytes_full)
        r.gauge("serving.cache.bytes_per_position.window",
                "bytes of K and V one position holds over the cache "
                "layers that keep a rolling window shorter than max_len"
                ).set(self._cache_bytes_window)
        r.gauge("serving.cache.layers",
                "KV cache layers in the carry (weight layers x passes "
                "over them)").set(self._cache_layers)
        r.gauge("serving.cache.bytes_per_position",
                "bytes of K and V one token position holds over all "
                "cache layers").set(self._cache_bytes_per_position)
        # prefix-cache instruments: hit classes as the ENGINE admitted
        # them (a shared cache's own stats() aggregate every engine),
        # bytes/slab gauges synced from the cache after each admission
        # round, and admission latency split by hit class — the
        # cached-vs-cold evidence bench.py --serve --prefix-mix reports
        self._c_prefix = {
            "full": r.counter("serving.prefix.hits_full",
                              "admissions served ENTIRELY from a cached "
                              "slab: zero prefill dispatches"),
            "partial": r.counter("serving.prefix.hits_partial",
                                 "admissions that prefilled only the "
                                 "uncached suffix"),
            "miss": r.counter("serving.prefix.misses",
                              "cold admissions (cache populated on the "
                              "way through)"),
        }
        self._c_prefix_insert = r.counter(
            "serving.prefix.insertions", "slabs inserted into the pool")
        self._c_prefix_evict = r.counter(
            "serving.prefix.evictions",
            "LRU slabs evicted past the byte budget")
        self._g_prefix_bytes = r.gauge(
            "serving.prefix.bytes_cached", "live slab bytes in the pool")
        self._g_prefix_slabs = r.gauge(
            "serving.prefix.slabs", "live slabs in the pool")
        self._c_tokens_saved = r.counter(
            "serving.prefill_tokens_saved",
            "prompt tokens whose prefill compute a cached prefix "
            "avoided")
        self._c_batched_groups = r.counter(
            "serving.admission.batched_groups",
            "admission rounds that batched several same-bucket "
            "(suffix-)prefills into one dispatch")
        self._c_disp_saved = r.counter(
            "serving.admission.dispatches_saved",
            "prefill dispatches avoided vs one-per-request admission "
            "(batched groups + full-prefix hits)")
        self._c_reordered = r.counter(
            "serving.admission.cache_reordered",
            "queued requests admitted ahead of an earlier-submitted "
            "same-priority peer because their prefix digest maximized "
            "slab reuse (cache-aware admission ordering)")
        self._h_admit = {
            cls: r.histogram(f"serving.admission_s.{cls}",
                             f"per-request admission wall time, "
                             f"{cls}-hit class")
            for cls in ("full", "partial", "miss")}
        # deadline machinery: sheds are typed refusals, expired rows are
        # partial returns — every path has its own counter so the bench
        # can account for EVERY accepted request
        self._c_shed_deadline = r.counter(
            "serving.shed.deadline",
            "submits refused typed: the deadline was already expired "
            "(shed before any prefill)")
        self._c_shed_backpressure = r.counter(
            "serving.shed.backpressure",
            "submits refused typed: estimated queue delay already "
            "blows the request's deadline")
        self._c_shed_queue = r.counter(
            "serving.shed.queue_deadline",
            "queued requests shed at admission: deadline expired while "
            "waiting (no prefill was ever dispatched)")
        self._c_deadline_rows = r.counter(
            "serving.deadline.expired_rows",
            "in-flight rows frozen at a chunk boundary past their "
            "deadline and returned partial (flagged deadline_expired)")
        self._c_snapshots = r.counter(
            "serving.snapshots", "resumable DecodeState snapshots "
            "written (crash-recovery cadence + graceful drain)")
        # fleet operations: live row migration + the finite guard
        self._c_corrupt_rows = r.counter(
            "serving.corrupt_rows",
            "rows whose harvested logits went NaN/Inf: frozen ALONE "
            "and returned partial (flagged corrupt_row) — the poison "
            "never spreads to the rest of the batch")
        self._c_migrated_out = r.counter(
            "serving.rows_migrated_out",
            "requests extracted off this engine by a live migration "
            "(ownership leaves with the payload)")
        self._c_migrated_in = r.counter(
            "serving.rows_migrated_in",
            "requests absorbed into this engine by a live migration")
        # device admission ring: the dispatch-boundary win is visible as
        # ring_scattered rows with ZERO host scatters — /metrics proof
        # that steady state is one fused dispatch per chunk
        self._c_ring_staged = r.counter(
            "serving.admission.ring_staged",
            "admitted rows staged into the device ring (their prefill "
            "dispatch scattered the row state device-side)")
        self._c_ring_scattered = r.counter(
            "serving.admission.ring_scattered",
            "staged rows spliced into the carry by a chunk program's "
            "ring prologue (no host round-trip, no extra dispatch)")
        self._c_ring_full = r.counter(
            "serving.admission.ring_full",
            "admissions deferred because the ring had no free row "
            "(un-admitted and re-queued at their tier's head)")
        self._c_host_scattered = r.counter(
            "serving.admission.host_scattered",
            "legacy host row-scatter admissions (prefix-cache/bundle "
            "paths; 0 whenever the device ring serves admission)")
        # speculative serving: cumulative verify-round economics (the
        # acceptance_len_mean gauge is the live tokens/dispatch lever)
        self._c_draft_prefill = r.counter(
            "serving.draft_prefill_dispatches",
            "draft-model admission prefills staged into the ring's "
            "draft caches (one per admission group under speculation)")
        self._c_spec_rounds = r.counter(
            "serving.spec.rounds",
            "draft/verify/accept rounds run for live rows")
        self._c_spec_accept = r.counter(
            "serving.spec.accepted_drafts",
            "draft tokens accepted by verification")
        self._c_spec_overflow = r.counter(
            "serving.spec.overflow_tokens",
            "tokens committed past the chunk boundary by a round that "
            "straddled it (the (B, T+K) buffer tail the harvest kept)")
        self._g_spec_accept_mean = r.gauge(
            "serving.spec.acceptance_len_mean",
            "cumulative accepted drafts per verify round")
        self._g_k_now = r.gauge(
            "serving.spec.k_now",
            "the draft depth K the next speculative chunk dispatches "
            "with (== num_speculative_tokens unless adaptive_k clamps "
            "it from the live acceptance mean)")
        if self._spec_configured:
            self._g_k_now.set(self._b.K)
        # multi-tenant LoRA serving: per-adapter row admissions, live
        # registry size and hot-swap applications — the /metrics proof
        # that mixed-tenant batches share the fused dispatch
        self._g_adapters_active = r.gauge(
            "serving.adapter.active",
            "adapters registered in this engine's AdapterStore")
        self._c_adapter_swaps = r.counter(
            "serving.adapter.swaps",
            "adapter hot-swaps applied between chunks (stacks re-merged "
            "after an update() once no in-flight row pinned the old "
            "revision)")
        self._c_adapter_rows: Dict[str, Any] = {}
        if adapter_store is not None:
            self._g_adapters_active.set(len(adapter_store))
        # per-latency-class streaming TTFT (histograms created on first
        # use; the HTTP front-end's flush cadence rides chunk harvests)
        self._h_stream_ttft: Dict[str, Any] = {}
        self._stream_cb: Dict[int, Any] = {}
        # crash recovery / replica identity
        self.replica_tag = None if replica_tag is None else str(replica_tag)
        self._snap_dir = snapshot_dir
        self._snap_every = int(snapshot_every_chunks or 0)
        if self._snap_every and not self._snap_dir:
            raise ValueError(
                "snapshot_every_chunks needs snapshot_dir to write into")
        self._snap_last_chunks = 0
        self._last_snapshot: Optional[Tuple[float, str]] = None
        self._last_prefix_stats = {"insertions": 0, "evictions": 0}
        self.slo_targets = {k: dict(v)
                            for k, v in (slo_targets or {}).items()}
        self._exporter = None
        # crash evidence: a ladder exhaustion's postmortem carries this
        # engine's registry snapshot (weakref — no lifetime extension),
        # and the prefix-cache occupancy/eviction state so a postmortem
        # shows what the cache held at crash time
        tag = (f"serving.{self.replica_tag}" if self.replica_tag
               else "serving")
        obs.flight_recorder.add_registry(tag, self.registry)
        if self.prefix_cache is not None:
            obs.flight_recorder.add_state(f"{tag}.prefix_cache",
                                          self.prefix_cache)

    @staticmethod
    def _resolve_prefix_cache(prefix_cache, bytes_, block):
        from paddle_tpu.serving.prefix_cache import (
            PrefixCache, resolve_prefix_cache_bytes)
        if prefix_cache is False:
            return None
        if isinstance(prefix_cache, PrefixCache):
            return prefix_cache
        budget = bytes_ if bytes_ is not None \
            else resolve_prefix_cache_bytes()
        if prefix_cache is None and not budget:
            return None           # default: flags/env say disabled
        if prefix_cache is not None and prefix_cache is not True:
            raise TypeError(
                f"prefix_cache must be None, a bool, or a PrefixCache, "
                f"got {type(prefix_cache).__name__}")
        return PrefixCache(bytes_budget=budget or None,
                           block_tokens=block)

    # legacy counter attributes, now views over the registry (pre-obs
    # callers and the bench dispatch-accounting asserts read these)
    @property
    def prefill_dispatches(self) -> int:
        return int(self._c_prefill.value)

    @property
    def chunk_dispatches(self) -> int:
        return int(self._c_chunk.value)

    @property
    def step_dispatches(self) -> int:
        return int(self._c_step.value)

    # -- submission --------------------------------------------------------
    # -- multi-tenant LoRA helpers -----------------------------------------
    def _adapter_tag(self, name: Optional[str]) -> Optional[str]:
        """The prefix-cache content tag for a request's adapter:
        ``"name@rev"`` (adapter KV is revision-specific content) or
        ``None`` for base rows — base digests stay byte-identical to a
        cache that never heard of adapters."""
        if name is None or self.adapter_store is None:
            return None
        return self.adapter_store.tag(name)

    def _adapter_row_counter(self, name: str):
        ctr = self._c_adapter_rows.get(name)
        if ctr is None:
            ctr = self.registry.counter(
                f"serving.adapter.rows.{name}",
                f"rows admitted for adapter {name!r} ('base' = no "
                f"adapter) — mixed names across one chunk ARE the "
                f"shared fused dispatch")
            self._c_adapter_rows[name] = ctr
        return ctr

    def _stream_ttft_hist(self, cls: str):
        h = self._h_stream_ttft.get(cls)
        if h is None:
            h = self.registry.histogram(
                f"serving.stream.ttft_s.{cls}",
                f"admission -> first streamed flush, latency class "
                f"{cls!r} (streaming submits only)")
            self._h_stream_ttft[cls] = h
        return h

    def apply_adapter_swap(self) -> bool:
        """Apply pending AdapterStore registrations/updates to the
        device stacks. Refused TYPED (:class:`AdapterVersionError`)
        while any in-flight row still decodes through a revision the
        swap would change — a KV cache computed under rev N continued
        under rev N+1 is neither tenant's output (the
        ``WeightVersionError`` argument, per adapter). ``step()``
        retries automatically each iteration; requests naming the
        pending revision queue until it lands. Returns True when the
        stacks moved."""
        store = self.adapter_store
        if store is None or store.version == self._b.lora_version:
            return False
        from paddle_tpu.serving.lora import AdapterVersionError
        for i, slot in self.scheduler.slots.occupied():
            ad = slot.request.adapter
            if ad is None or slot.adapter_rev is None:
                continue
            cur = store.revision(ad)
            if cur != slot.adapter_rev:
                raise AdapterVersionError(
                    f"adapter {ad!r} staged rev {cur} but request "
                    f"{slot.request.id} (slot {i}) still decodes "
                    f"through rev {slot.adapter_rev}; the swap applies "
                    f"once those rows drain",
                    adapter=ad, pinned_rev=slot.adapter_rev,
                    store_rev=cur)
        if self._b.refresh_adapters():
            self._c_adapter_swaps.inc()
            self._g_adapters_active.set(len(store))
            self._served_rev = {n: store.revision(n)
                                for n in store.names()}
            obs.tracer.event("serving.adapter.swap",
                             version=store.version)
            return True
        return False

    def submit(self, prompt, max_new_tokens: int,
               eos_token_id: Optional[int] = None,
               temperature: float = 1.0, seed: int = 0,
               priority: int = 0, latency_class: str = "default",
               slo_ttft_s: Optional[float] = None,
               slo_latency_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               rng_request_id: Optional[int] = None,
               rng_tokens_emitted: int = 0,
               adapter: Optional[str] = None,
               speculative: Optional[bool] = None,
               on_tokens=None) -> int:
        """Queue one request; returns its id (results key).
        ``latency_class`` + optional per-request SLO targets feed the
        per-class TTFT/latency violation counters. ``deadline_s`` is a
        HARD budget in seconds from now: an already-expired budget and a
        queue whose estimated delay blows it are shed here with a typed
        :class:`DeadlineExceededError` (``serving.shed.deadline`` /
        ``serving.shed.backpressure``) — the request never costs a
        prefill; a request that expires later is shed at admission or
        frozen partial between chunks. ``rng_request_id`` /
        ``rng_tokens_emitted`` feed the ``request_keyed_rng`` stream
        derivation (a router passes its stable request id and, on a
        replay, how many generated tokens the prompt already carries);
        ignored under the default seed-only rule.
        ``adapter``: serve this request through a registered LoRA
        adapter's deltas (``adapter_store=``); unknown names are a typed
        :class:`~paddle_tpu.serving.lora.UnknownAdapterError` here,
        before any slot work. ``None`` = the base model.
        ``speculative=False`` opts this request OUT of speculative
        decoding on a draft-equipped engine (its row runs plain
        verify-free decode inside the same fused dispatch); ``None`` =
        the engine default. ``on_tokens``: per-token streaming callback
        ``(request_id, np.ndarray new_tokens, final: bool)`` fired at
        every chunk harvest with the tokens the row gained since the
        last call, then once with ``final=True`` at finish."""
        from paddle_tpu.inference.generate import _normalize_eos
        from paddle_tpu.runtime.resilience import DeadlineExceededError
        if adapter is not None:
            from paddle_tpu.serving.lora import UnknownAdapterError
            if self.adapter_store is None:
                raise UnknownAdapterError(
                    f"request names adapter {adapter!r} but this engine "
                    f"serves no AdapterStore (pass adapter_store=)")
            self.adapter_store.index(adapter)   # typed unknown-name check
        if speculative and not self._spec_configured:
            raise ValueError(
                "submit(speculative=True) needs a draft_model-equipped "
                "engine")
        prompt = np.asarray(prompt)
        if prompt.ndim == 2:
            if prompt.shape[0] != 1:
                raise ValueError(
                    f"submit takes ONE request (a (S,) or (1, S) prompt), "
                    f"got batch {prompt.shape[0]}; call submit per row")
            prompt = prompt[0]
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got shape {prompt.shape}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        bucket = self.scheduler.bucket(len(prompt))
        # speculative rows need K extra cache rows of slack: a verify
        # dispatch writes K+1 positions past the last committed token
        slack = self._b.K if self._spec_configured else 0
        if max(bucket,
               len(prompt) + int(max_new_tokens) + slack) > self._b.max_len:
            extra = (f" + {slack} speculative lookahead slack"
                     if slack else "")
            raise ValueError(
                f"prompt {len(prompt)} (bucket {bucket}) + "
                f"{max_new_tokens} new tokens{extra} exceeds the "
                f"backend's max_len {self._b.max_len}")
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                # the cheapest shed: the budget is gone before any work
                self._c_shed_deadline.inc()
                obs.tracer.event("serving.request.shed",
                                 reason="deadline_expired",
                                 deadline_s=deadline_s)
                raise DeadlineExceededError(
                    f"request deadline ({deadline_s:.4f}s) already "
                    f"expired at submit; shed before any prefill")
            est = self.estimated_queue_delay_s()
            if est > deadline_s:
                self._c_shed_backpressure.inc()
                obs.tracer.event("serving.request.shed",
                                 reason="backpressure",
                                 estimated_queue_delay_s=round(est, 6),
                                 deadline_s=deadline_s)
                raise DeadlineExceededError(
                    f"estimated queue delay {est:.4f}s (depth "
                    f"{len(self.scheduler)} over {self.num_slots} "
                    f"slots) already exceeds the {deadline_s:.4f}s "
                    f"deadline; shed at submit")
        rid = self._next_id
        self._next_id += 1
        now = time.monotonic()
        req = Request(
            id=rid, prompt=prompt, max_new_tokens=int(max_new_tokens),
            eos_token_id=_normalize_eos(eos_token_id),
            temperature=float(temperature), seed=int(seed),
            priority=int(priority), submit_time=now,
            latency_class=str(latency_class),
            slo_ttft_s=slo_ttft_s, slo_latency_s=slo_latency_s,
            deadline_s=deadline_s,
            rng_request_id=(None if rng_request_id is None
                            else int(rng_request_id)),
            rng_tokens_emitted=int(rng_tokens_emitted),
            adapter=adapter,
            speculative=(None if speculative is None
                         else bool(speculative)))
        if self.scheduler.cache_aware:
            # the cache-aware ordering's grouping key: the prompt's
            # FIRST block-boundary digest (the shortest ladder entry) —
            # requests sharing >= one hash block group together.
            # Adapter KV is adapter-specific content, so the tag seeds
            # the digest chain: same prompt, different tenant -> a
            # DIFFERENT group (and a guaranteed cache miss).
            from paddle_tpu.serving.prefix_cache import prefix_digests
            req.prefix_group = prefix_digests(
                prompt, self.prefix_cache.block_tokens,
                adapter=self._adapter_tag(adapter))[-1][1]
        if on_tokens is not None:
            self._stream_cb[rid] = on_tokens
        self.scheduler.push(req)
        self._mark_between_steps(now)
        self._g_qdepth.set(len(self.scheduler))
        obs.tracer.event("serving.request.queued", request=rid,
                         prompt_len=len(prompt),
                         max_new_tokens=int(max_new_tokens))
        return rid

    def estimated_queue_delay_s(self) -> float:
        """The backpressure signal: how long a NEW submit would likely
        wait for a slot — (queued ahead / slots) admission waves at the
        observed mean request wall time. 0.0 until a request has
        finished (no evidence, no shedding)."""
        lat = self._h_latency
        if not lat.count or not len(self.scheduler):
            return 0.0
        return len(self.scheduler) / self.num_slots * lat.mean

    # -- the serving loop --------------------------------------------------
    def step(self) -> List[Tuple[int, Any]]:
        """One iteration: shed/freeze expired deadlines, admit into free
        slots, run ONE chunk dispatch, harvest finished rows. Returns
        ``[(request_id, result), ...]`` finished this step (also
        retrievable via ``result(id)``). A request shed for an expired
        deadline finishes as a typed ``DeadlineExceededError`` VALUE in
        the list (and in ``result(id)``) — accepted work always resolves
        to tokens or a typed error."""
        now = time.monotonic()
        self._phase_to("admit")
        try:
            return self._step(now)
        finally:
            self._phase_to(None)

    def _phase_to(self, name: Optional[str]) -> None:
        """Close the running step's open phase and open ``name`` (None:
        close only). The phases follow each other without a gap, so
        their histograms tile ``step()``: admit -> dispatch -> wait ->
        harvest; the degradation rungs go back to dispatch. The device
        timeline moves on the same clock reading: the end of ``wait`` is
        the end of the blocking read behind the chunk (device known
        empty), and ``wait`` itself is no place of the host's — it is
        blocked on a fed device."""
        ph, self._open_phase = self._open_phase, None
        now = None
        if ph is not None:
            ph.__exit__(None, None, None)
            now = ph.t1
        if name is not None:
            self._open_phase = obs.phase(
                "serving.step." + name, self._h_phase[name]).__enter__()
            now = self._open_phase.t0
        if name != "wait":
            self._timeline.host(
                self._between_steps() if name is None else name, now)
        if ph is not None and ph.hist is self._h_phase["wait"]:
            self._timeline.drained(now)

    def _between_steps(self) -> Optional[str]:
        """Where the device timeline has the host outside ``step()``:
        ``outside`` with work submitted and unfinished, None with none."""
        return "outside" if (len(self.scheduler)
                             or self.scheduler.slots.occupied()) else None

    def _mark_between_steps(self, now: Optional[float] = None) -> None:
        """Work came or went outside ``step()`` (submit, restore,
        absorb_rows, extract_rows): from here the caller keeps the device
        waiting, or nobody does."""
        if self._open_phase is None:
            self._timeline.host(self._between_steps(), now)

    def _step(self, now: float) -> List[Tuple[int, Any]]:
        if self.adapter_store is not None and \
                self.adapter_store.version != self._b.lora_version:
            from paddle_tpu.serving.lora import AdapterVersionError
            try:
                # staged hot-swap: applies the moment no in-flight row
                # pins a changed revision (callers wanting the typed
                # refusal call apply_adapter_swap() directly)
                self.apply_adapter_swap()
            except AdapterVersionError:
                pass
        pre = self._enforce_deadlines(now)
        self._h_qdepth.observe(len(self.scheduler))
        admitted = self.scheduler.admissions()
        if self.scheduler.cache_reordered > int(self._c_reordered.value):
            self._c_reordered.inc(self.scheduler.cache_reordered
                                  - int(self._c_reordered.value))
        if admitted:
            self._admit_all(admitted, now)
        self._g_qdepth.set(len(self.scheduler))
        occupied = self.scheduler.slots.occupied()
        if not occupied:
            return pre
        self._phase_to("dispatch")
        self._h_occ.observe(len(occupied) / self.num_slots)
        self._c_live_kv.inc(sum(slot.kv_pos for _, slot in occupied))
        if self._b.eva is not None:
            W, C = self._b.eva
            T = self.chunk_size
            at = [slot.kv_pos for _, slot in occupied]
            self._c_live_win.inc(sum(n % W + 1 for n in at))
            self._c_live_sum.inc(sum(n // W * (W // C) for n in at))
            self._c_eva_chunks.inc(sum((n + T) // C - n // C for n in at))
            self._c_eva_ends.inc(sum((n + T) // W - n // W for n in at))
        elif self._cache_bytes_window:
            self._c_live_win.inc(sum(min(slot.kv_pos, self._window_len)
                                     for _, slot in occupied))
        if self._b.latent_layers:
            L = self._b.max_len
            self._c_live_latent.inc(self._b.latent_layers * sum(
                min(slot.kv_pos + t + 1, L) for _, slot in occupied
                for t in range(self.chunk_size)))
        toks = self._dispatch_chunk(occupied)
        nv = self._last_nv
        t_chunk_done = time.monotonic()
        self._phase_to("harvest")
        # finite guard: one harvest-time check over the post-chunk
        # logits. A numerically poisoned row (NaN/Inf) is frozen ALONE
        # and returned partial — one bad row must never take down the
        # whole batch or, worse, migrate its poison into a peer's carry
        logits_h, moe_h = jax.device_get((self.state.logits,
                                          list(self._b.moe_counts)))
        row_finite = np.isfinite(np.asarray(logits_h)).all(axis=-1)
        for pairs, touched, load in moe_h:
            self._c_moe_pairs.inc(int(pairs))
            self._c_moe_touched.inc(int(touched))
            self._g_moe_load.set(int(load))
        if moe_h:
            self._b.moe_counts.clear()
        sr = sa = None
        if self._spec_active and self.state.spec_rounds is not None:
            # mirror the carry's per-row cumulative acceptance stats
            # (reset by the ring prologue at admission, so each slot's
            # values are exact per-request totals across chunk
            # re-entries — never stale, never last-chunk-only)
            sr = np.asarray(jax.device_get(self.state.spec_rounds))
            sa = np.asarray(jax.device_get(self.state.spec_accepted))
        finished, freed = [], []
        for i, slot in occupied:
            slot.chunks += 1
            if not row_finite[i]:
                req = slot.request
                # the chunk that surfaced the corruption is dropped:
                # tokens sampled off non-finite logits are noise; the
                # pre-chunk prefix is the honest partial
                seq = (np.concatenate(slot.tokens) if slot.tokens
                       else np.zeros((0,), np.int64))
                seq = seq[:req.max_new_tokens]
                self._c_corrupt_rows.inc()
                obs.record_crash(
                    "serving.corrupt_row",
                    error=FloatingPointError(
                        f"non-finite logits in carry row {i} "
                        f"(request {req.id}) after chunk {slot.chunks}"),
                    extra={"request": int(req.id), "slot": int(i),
                           "chunks": int(slot.chunks),
                           "tokens_kept": int(seq.shape[0])})
                res = self._finish(slot, seq, i, corrupt_row=True)
                self._results[req.id] = res
                finished.append((req.id, res))
                if slot.pinned_slab is not None:
                    self.prefix_cache.unpin(slot.pinned_slab)
                    slot.pinned_slab = None
                self.scheduler.slots.release(i)
                freed.append(i)
                continue
            # speculative chunks run T verify rounds and return a wide
            # buffer with a per-row valid count >= T: the acceptance
            # overflow is kept, not re-generated, so the dispatch
            # reduction survives chunk boundaries
            slot.tokens.append(toks[i] if nv is None
                               else toks[i][:int(nv[i])])
            slot.kv_pos += len(slot.tokens[-1])
            if sr is not None:
                dr = int(sr[i]) - slot.spec_rounds
                da = int(sa[i]) - slot.spec_accepted
                if dr > 0:
                    self._c_spec_rounds.inc(dr)
                    slot.spec_rounds = int(sr[i])
                if da > 0:
                    self._c_spec_accept.inc(da)
                    slot.spec_accepted = int(sa[i])
                if nv is not None:
                    ov = int(nv[i]) - self.chunk_size
                    if ov > 0:
                        slot.spec_overflow += ov
                        self._c_spec_overflow.inc(ov)
            if slot.first_token_at is None:
                # the slot's first tokens reached the host with THIS
                # dispatch: admission -> here is the request's TTFT
                slot.first_token_at = t_chunk_done
                self._h_ttft.observe(t_chunk_done - slot.admitted_at)
                self._h_ttft_submit.observe(
                    t_chunk_done - slot.request.submit_time)
            req = slot.request
            seq = np.concatenate(slot.tokens)
            fin = False
            if req.eos_token_id is not None:
                hit = seq == req.eos_token_id
                if hit.any():
                    seq = seq[:int(np.argmax(hit)) + 1]
                    fin = True
            if len(seq) >= req.max_new_tokens:
                seq = seq[:req.max_new_tokens]
                fin = True
            if not fin:
                cb = self._stream_cb.get(req.id)
                if cb is not None and len(seq) > slot.streamed:
                    # per-token streaming: flush the tokens this chunk
                    # harvest added (the flush cadence IS the chunk
                    # boundary; _finish fires the final flush)
                    if slot.streamed == 0:
                        self._stream_ttft_hist(req.latency_class)\
                            .observe(t_chunk_done - slot.admitted_at)
                    new = seq[slot.streamed:]
                    slot.streamed = int(len(seq))
                    cb(req.id, np.asarray(new), False)
                continue
            res = self._finish(slot, seq, i)
            self._results[req.id] = res
            finished.append((req.id, res))
            if slot.pinned_slab is not None:
                # the request's slab outlived its flight: unpinned, it
                # becomes evictable again (refcount pinning contract)
                self.prefix_cache.unpin(slot.pinned_slab)
                slot.pinned_slab = None
            self.scheduler.slots.release(i)
            freed.append(i)
        if sr is not None:
            rt = int(self._c_spec_rounds.value)
            if rt:
                mean = int(self._c_spec_accept.value) / rt
                self._g_spec_accept_mean.set(mean)
                if self.adaptive_k:
                    # clamp the NEXT chunk's draft depth from the live
                    # acceptance mean: drafting far past what verify
                    # accepts is pure wasted draft+verify compute, while
                    # high acceptance earns the full K. EWMA smooths the
                    # chunk-to-chunk noise; each distinct K compiles
                    # once (it's a static), so k_now moving is a cache
                    # hit after the first visit.
                    e = self._accept_ewma
                    self._accept_ewma = (mean if e is None
                                         else 0.8 * e + 0.2 * mean)
                    knew = max(1, min(self._b.K,
                                      int(np.ceil(self._accept_ewma))
                                      + 1))
                    if knew != self._k_now:
                        self._k_now = knew
                        self._g_k_now.set(knew)
        if freed:
            self._freeze_rows(freed)
        if self._snap_every and (self.chunk_dispatches
                                 - self._snap_last_chunks
                                 >= self._snap_every):
            # cadence snapshot at the END of the step: the carry and the
            # host token buffers agree here (every dispatched chunk's
            # tokens are already in slot.tokens)
            self.snapshot(self._snap_dir)
        return pre + finished

    def _freeze_rows(self, rows: Sequence[int]) -> None:
        """Freeze carry rows until re-admission (freed slots and expired
        deadlines): they keep riding the batched program, but pinned —
        their output is discarded. A fixed-shape (B,) mask OR, not a
        scatter: eager scatters recompile per freed-set shape (~ms each
        on the host path)."""
        import jax.numpy as jnp
        mask = np.zeros(self.num_slots, bool)
        mask[list(rows)] = True
        self.state = dataclasses.replace(
            self.state,
            done=jnp.logical_or(self.state.done, jnp.asarray(mask)))

    def _enforce_deadlines(self, now: float) -> List[Tuple[int, Any]]:
        """The two non-submit deadline enforcement points, swept at the
        top of every step: (a) queued requests whose deadline passed are
        shed TYPED before they cost a prefill; (b) in-flight rows past
        their deadline are frozen like EOS and finished PARTIAL, flagged
        ``deadline_expired`` — the slot frees for the next admission.
        Returns the ``(request_id, outcome)`` pairs resolved here."""
        from paddle_tpu.runtime.resilience import DeadlineExceededError
        out: List[Tuple[int, Any]] = []
        for req in self.scheduler.shed_expired(now):
            self._c_shed_queue.inc()
            err = DeadlineExceededError(
                f"request {req.id} deadline expired after "
                f"{now - req.submit_time:.4f}s in queue "
                f"(budget {req.deadline_s:.4f}s); shed at admission",
                request_id=req.id)
            self._results[req.id] = err
            out.append((req.id, err))
            cb = self._stream_cb.pop(req.id, None)
            if cb is not None:
                # a shed streaming request still terminates its stream
                cb(req.id, np.zeros((0,), np.int64), True)
            obs.tracer.event("serving.request.shed", request=req.id,
                             reason="queue_deadline")
        frozen = []
        for i, slot in self.scheduler.slots.occupied():
            req = slot.request
            if req.deadline_at is None or now <= req.deadline_at:
                continue
            seq = (np.concatenate(slot.tokens) if slot.tokens
                   else np.zeros((0,), np.int64))
            seq = seq[:req.max_new_tokens]
            self._c_deadline_rows.inc()
            res = self._finish(slot, seq, i, deadline_expired=True)
            self._results[req.id] = res
            out.append((req.id, res))
            if slot.pinned_slab is not None:
                self.prefix_cache.unpin(slot.pinned_slab)
                slot.pinned_slab = None
            self.scheduler.slots.release(i)
            frozen.append(i)
        if frozen:
            self._freeze_rows(frozen)
        return out

    def drain(self, max_steps: Optional[int] = None,
              deadline_s: Optional[float] = None,
              snapshot_path: Optional[str] = None) -> Dict[int, Any]:
        """Step until the queue and every slot are empty; returns
        ``{request_id: outcome}`` for everything finished while draining
        (outcomes are results or typed deadline errors).

        ``deadline_s`` is the GRACEFUL-DRAIN budget: when it runs out
        with work still in flight, the engine snapshots the carry +
        bookkeeping to ``snapshot_path`` (or the engine's
        ``snapshot_dir``) instead of discarding accepted work, and
        returns what finished — ``restore()`` on a fresh engine resumes
        the rest bit-exactly. No snapshot destination configured raises
        ``ValueError`` up front, not after the budget is spent."""
        if deadline_s is not None and not (snapshot_path
                                           or self._snap_dir):
            raise ValueError(
                "drain(deadline_s=) needs snapshot_path or an engine "
                "snapshot_dir: a graceful drain SNAPSHOTS unfinished "
                "work, it never discards it")
        t0 = time.monotonic()
        out: Dict[int, Any] = {}
        steps = 0
        while len(self.scheduler) or self.scheduler.slots.occupied():
            if deadline_s is not None \
                    and time.monotonic() - t0 > deadline_s:
                self.snapshot(snapshot_path or self._snap_dir)
                break
            for rid, res in self.step():
                out[rid] = res
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"drain did not converge within {max_steps} steps")
        return out

    def result(self, request_id: int):
        return self._results.get(request_id)

    # -- crash recovery: DecodeState snapshot / restore --------------------
    _SNAP_DATA = "state.npz"
    _SNAP_MANIFEST = "manifest.json"

    def snapshot(self, path: str) -> str:
        """Serialize everything needed to resume THIS engine's accepted
        work into directory ``path``: the full ``DecodeState`` carry
        (quantized ``{"q","s"}`` leaves flatten like any other pytree;
        a mesh-sharded carry is gathered process-locally) plus the slot
        table's requests-with-tokens-so-far and the queued requests.
        Written as one npz payload under an atomic sha256 manifest (the
        PR-3 checkpoint discipline: the digest is hashed from intended
        bytes BEFORE disk, writes go through ``atomic_write_bytes``, so
        a torn/flipped file is refused typed at restore, never resumed
        wrong). Snapshots are taken at chunk boundaries only — the carry
        and the host token buffers agree there — which makes the greedy
        continuation after ``restore()`` bit-exact."""
        import jax

        from paddle_tpu.distributed.checkpoint import _np_storable
        from paddle_tpu.runtime.resilience import atomic_write_bytes
        if self._spec_configured:
            raise ValueError(
                "speculative serving does not snapshot yet: the draft "
                "cache / pending-token carry is not in the snapshot "
                "payload; serve without draft_model= to snapshot")
        if any(m is not None for m in self._ring_meta):
            raise RuntimeError(
                "snapshot() with staged-but-unscattered admission ring "
                "rows: run one more step() so the pending ring splice "
                "lands in the carry, then snapshot at the chunk "
                "boundary")
        os.makedirs(path, exist_ok=True)
        st = self.state
        leaves, _ = jax.tree_util.tree_flatten(
            (st.logits, st.kc, st.vc, st.pos, st.keys, st.done, st.eos,
             st.temp))
        arrays: Dict[str, np.ndarray] = {}
        leaf_meta = []
        for i, leaf in enumerate(leaves):
            store, tag = _np_storable(np.asarray(jax.device_get(leaf)))
            arrays[f"leaf_{i}"] = store
            leaf_meta.append({"dtype": tag})
        now = time.monotonic()
        slots_meta = []
        for i, slot in self.scheduler.slots.occupied():
            arrays[f"slot{i}_prompt"] = np.asarray(slot.request.prompt)
            for j, piece in enumerate(slot.tokens):
                arrays[f"slot{i}_piece{j}"] = np.asarray(piece)
            slots_meta.append({"slot": i,
                               "request": self._req_meta(slot.request,
                                                         now),
                               "pieces": len(slot.tokens),
                               "chunks": slot.chunks})
        queue_meta = []
        for j, req in enumerate(self.scheduler.queued()):
            arrays[f"queue{j}_prompt"] = np.asarray(req.prompt)
            queue_meta.append(self._req_meta(req, now))
        meta = {
            "kind": "paddle_tpu.decode_snapshot", "version": 1,
            "time_unix": time.time(),
            "num_slots": self.num_slots, "chunk_size": self.chunk_size,
            "quant": self._b.quant,
            "mesh_axes": (dict(self._b.sharding.axes)
                          if self._b.sharding is not None else None),
            "steps_done": int(st.steps_done),
            "next_id": self._next_id,
            "leaves": leaf_meta, "slots": slots_meta,
            "queue": queue_meta,
        }
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        payload = buf.getvalue()
        manifest = {"kind": meta["kind"], "data": self._SNAP_DATA,
                    "sha256": hashlib.sha256(payload).hexdigest(),
                    "bytes": len(payload), "meta": meta}
        # data first, manifest second: a crash between the two leaves a
        # digest mismatch -> typed refusal at restore, never a silent
        # half-new snapshot
        atomic_write_bytes(os.path.join(path, self._SNAP_DATA), payload)
        atomic_write_bytes(os.path.join(path, self._SNAP_MANIFEST),
                           json.dumps(manifest, indent=1).encode())
        self._c_snapshots.inc()
        self._snap_last_chunks = self.chunk_dispatches
        self._last_snapshot = (time.monotonic(), path)
        obs.tracer.event("serving.snapshot", path=path,
                         in_flight=len(slots_meta),
                         queued=len(queue_meta))
        return path

    def restore(self, path: str) -> Dict[str, int]:
        """Resume a :meth:`snapshot` on a FRESH engine built over the
        same-shape backend: verifies the sha256 manifest (typed
        ``CorruptCheckpointError`` on a torn/flipped/missing file),
        cross-checks slot count, quant recipe
        (``QuantMismatchError``) and mesh topology
        (``MeshMismatchError``), then rebuilds the carry on device —
        under the backend's NamedShardings when meshed — and the
        slot/queue bookkeeping. A snapshot taken with FEWER slots than
        this engine row-remaps: its rows land in ``[0:snap_slots]`` and
        the remaining rows stay free (a survivor absorbing a smaller
        dead replica's carry); a larger snapshot is refused. Greedy
        continuation is bit-exact with the run the snapshot
        interrupted. Returns ``{"in_flight": n, "queued": m,
        "remapped_rows": r}`` (``r`` = 0 on an exact-shape restore)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.distributed.checkpoint import _np_restore
        from paddle_tpu.inference.sharding import MeshMismatchError
        from paddle_tpu.runtime.resilience import CorruptCheckpointError
        from paddle_tpu.serving.scheduler import Slot
        if self._spec_configured:
            raise ValueError(
                "speculative serving does not snapshot yet: restore "
                "into an engine built without draft_model=")
        if self._next_id or len(self.scheduler) \
                or self.scheduler.slots.occupied():
            raise RuntimeError(
                "restore() needs a fresh engine (no submissions yet): "
                "build a new ServingEngine over the same backend shape "
                "and restore into that")
        mpath = os.path.join(path, self._SNAP_MANIFEST)
        dpath = os.path.join(path, self._SNAP_DATA)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as e:
            raise CorruptCheckpointError(
                f"snapshot manifest unreadable at {mpath}: {e}") from e
        try:
            with open(dpath, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise CorruptCheckpointError(
                f"snapshot data missing at {dpath}: {e}") from e
        got = hashlib.sha256(raw).hexdigest()
        want = manifest.get("sha256", "")
        if got != want:
            raise CorruptCheckpointError(
                f"snapshot data is corrupt: sha256 {got[:16]}… != "
                f"manifest {want[:16]}… — refusing to resume from a "
                f"torn/corrupt snapshot")
        meta = manifest["meta"]
        snap_slots = int(meta["num_slots"])
        if snap_slots > self.num_slots:
            raise ValueError(
                f"snapshot was taken with num_slots="
                f"{meta['num_slots']}, this engine has only "
                f"{self.num_slots}; a snapshot restores 1:1 or INTO a "
                f"larger batch (row-remapping), never a smaller one")
        if meta.get("quant") != self._b.quant:
            from paddle_tpu.quantization.kv_cache import \
                QuantMismatchError
            raise QuantMismatchError(
                f"snapshot carries quant recipe "
                f"{meta.get('quant') or 'none'!r} but this engine's "
                f"backend serves {self._b.quant or 'none'!r}")
        have_axes = (dict(self._b.sharding.axes)
                     if self._b.sharding is not None else None)
        if meta.get("mesh_axes") != have_axes:
            raise MeshMismatchError(
                f"snapshot recorded mesh {meta.get('mesh_axes')} but "
                f"this engine serves {have_axes}")
        npz = np.load(io.BytesIO(raw), allow_pickle=False)
        template = self._b.new_state()
        tleaves, treedef = jax.tree_util.tree_flatten(
            (template.logits, template.kc, template.vc, template.pos,
             template.keys, template.done, template.eos, template.temp))
        lm = meta["leaves"]
        if len(lm) != len(tleaves):
            raise CorruptCheckpointError(
                f"snapshot carry layout mismatch: {len(lm)} leaves "
                f"recorded, backend expects {len(tleaves)}")
        leaves = []
        for i, (tl, m) in enumerate(zip(tleaves, lm)):
            arr = _np_restore(npz[f"leaf_{i}"], m["dtype"])
            tshape = tuple(tl.shape)
            if tuple(arr.shape) == tshape:
                leaves.append(jnp.asarray(arr))
                continue
            # row-remapping restore (snap_slots < num_slots): the ONLY
            # tolerated shape delta is the batch axis shrinking from
            # this engine's num_slots to the snapshot's — the smaller
            # snapshot's rows scatter into [0:snap_slots] and the tail
            # rows keep the fresh template's free-row state (a survivor
            # absorbing a smaller dead replica's carry)
            diff = ([ax for ax, (a, b) in
                     enumerate(zip(arr.shape, tshape)) if a != b]
                    if arr.ndim == tl.ndim else [])
            if (snap_slots == self.num_slots or len(diff) != 1
                    or arr.shape[diff[0]] != snap_slots
                    or tshape[diff[0]] != self.num_slots):
                raise CorruptCheckpointError(
                    f"snapshot leaf {i} has shape {arr.shape}, backend "
                    f"expects {tshape} (snapshot rows {snap_slots}, "
                    f"engine rows {self.num_slots})")
            full = np.asarray(jax.device_get(tl)).copy()
            idx = [slice(None)] * full.ndim
            idx[diff[0]] = slice(0, snap_slots)
            full[tuple(idx)] = arr
            leaves.append(jnp.asarray(full))
        logits, kc, vc, pos, keys, done, eos, temp = \
            jax.tree_util.tree_unflatten(treedef, leaves)
        st = dataclasses.replace(
            template, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
            done=done, eos=eos, temp=temp,
            steps_done=int(meta["steps_done"]))
        if self._b.sharding is not None:
            st = self._b.sharding.put_state(st, self._b.head_major)
        self.state = st
        now = time.monotonic()
        for sm in meta["slots"]:
            i = int(sm["slot"])
            req = self._req_from_meta(sm["request"],
                                      npz[f"slot{i}_prompt"], now)
            self.scheduler.slots.entries[i] = Slot(
                request=req, admitted_at=now, chunks=int(sm["chunks"]),
                tokens=[np.asarray(npz[f"slot{i}_piece{j}"])
                        for j in range(int(sm["pieces"]))])
        if st.adapter_idx is not None:
            # the adapter routing is bookkeeping, not carry payload:
            # rebuild each restored row's index from its request's
            # adapter name (unknown names refuse typed — the store must
            # know every adapter the snapshot's rows decode through)
            ai = np.zeros((self.num_slots,), np.int32)
            for sm in meta["slots"]:
                ad = sm["request"].get("adapter")
                if ad is not None:
                    ai[int(sm["slot"])] = self.adapter_store.index(ad)
                    self.scheduler.slots.entries[
                        int(sm["slot"])].adapter_rev = \
                        self.adapter_store.revision(ad)
            aidx = jnp.asarray(ai)
            if self._b.sharding is not None:
                aidx = self._b.sharding.put_state_field(
                    "adapter_idx", aidx, self._b.head_major)
            self.state = dataclasses.replace(self.state,
                                             adapter_idx=aidx)
        for j, qm in enumerate(meta["queue"]):
            self.scheduler.push(
                self._req_from_meta(qm, npz[f"queue{j}_prompt"], now))
        self._next_id = int(meta["next_id"])
        self._g_qdepth.set(len(self.scheduler))
        self._mark_between_steps()
        obs.tracer.event("serving.restore", path=path,
                         in_flight=len(meta["slots"]),
                         queued=len(meta["queue"]))
        return {"in_flight": len(meta["slots"]),
                "queued": len(meta["queue"]),
                "remapped_rows": (snap_slots
                                  if snap_slots != self.num_slots else 0)}

    @staticmethod
    def _req_meta(req: Request, now: float) -> dict:
        """The serialized-request record shared by :meth:`snapshot` and
        :meth:`extract_rows`; :meth:`_req_from_meta` is its inverse."""
        return {
            "id": req.id, "max_new_tokens": req.max_new_tokens,
            "eos_token_id": req.eos_token_id,
            "temperature": req.temperature, "seed": req.seed,
            "priority": req.priority,
            "latency_class": req.latency_class,
            "slo_ttft_s": req.slo_ttft_s,
            "slo_latency_s": req.slo_latency_s,
            # deadlines cross the payload as REMAINING budget: the
            # monotonic clock does not survive a process restart
            "deadline_remaining_s": (
                None if req.deadline_at is None
                else req.deadline_at - now),
            "rng_request_id": req.rng_request_id,
            "rng_tokens_emitted": req.rng_tokens_emitted,
            "adapter": req.adapter,
            "speculative": req.speculative,
        }

    @staticmethod
    def _req_from_meta(m: dict, prompt: np.ndarray, now: float) -> Request:
        rem = m.get("deadline_remaining_s")
        return Request(
            id=int(m["id"]), prompt=np.asarray(prompt),
            max_new_tokens=int(m["max_new_tokens"]),
            eos_token_id=m.get("eos_token_id"),
            temperature=float(m["temperature"]), seed=int(m["seed"]),
            priority=int(m["priority"]), submit_time=now,
            latency_class=m.get("latency_class", "default"),
            slo_ttft_s=m.get("slo_ttft_s"),
            slo_latency_s=m.get("slo_latency_s"),
            # a deadline crosses the snapshot as remaining budget; an
            # already-negative remainder is swept typed on the first
            # post-restore step (no zombie work)
            deadline_s=rem,
            deadline_at=None if rem is None else now + rem,
            rng_request_id=m.get("rng_request_id"),
            rng_tokens_emitted=int(m.get("rng_tokens_emitted") or 0),
            adapter=m.get("adapter"),
            speculative=m.get("speculative"))

    # -- replica plumbing (serving/router.py reads these) ------------------
    def export_inflight(self) -> List[Tuple[Request, np.ndarray, int]]:
        """``(request, tokens generated so far, chunk pieces)`` per
        occupied slot — the requeue payload the router reads off a dead
        replica. Host bookkeeping only: the pieces were harvested chunk
        by chunk (each exactly once, in order), so replaying them is
        dedup-safe by construction."""
        out = []
        for _, slot in self.scheduler.slots.occupied():
            toks = (np.concatenate(slot.tokens) if slot.tokens
                    else np.zeros((0,), np.int64))
            out.append((slot.request, toks, len(slot.tokens)))
        return out

    def take_queued(self) -> List[Request]:
        """Pop every queued request (requeue export of a dead replica)."""
        taken = self.scheduler.take_all()
        self._g_qdepth.set(0)
        return taken

    def clear_inflight(self) -> None:
        """Release every occupied slot — the dead-replica fence: the
        work was exported for requeue, so the slot table must not keep
        claiming it (a later ``unfence`` + ``reset_state`` reuses the
        engine cleanly)."""
        for i, slot in self.scheduler.slots.occupied():
            if slot.pinned_slab is not None:
                self.prefix_cache.unpin(slot.pinned_slab)
                slot.pinned_slab = None
            self.scheduler.slots.release(i)

    def reset_state(self) -> None:
        """Rebuild a fresh carry (every slot free) — the unfence path:
        a revived replica must not resume on whatever the dead dispatch
        left behind."""
        if self.scheduler.slots.occupied():
            raise RuntimeError(
                "reset_state with occupied slots would orphan in-flight "
                "requests; export/clear them first")
        self.state = self._b.new_state()
        # rows staged in the admission ring were bound for the old carry
        # (their requests are cleared with the slots): left in place they
        # hold the ring full for good and every admission re-queues
        self._ring_meta = [None] * self._ring_slots

    # -- live row migration (serving/cluster fleet operations) -------------
    def extract_rows(self, request_ids) -> Dict[str, Any]:
        """The row-SUBSET generalization of :meth:`snapshot`: serialize
        only the selected requests into one migration payload. An
        in-flight request ships its carry rows — logits / KV / pos /
        the LIVE RNG key / eos / temp, gathered on the batch axis —
        plus the slot bookkeeping (tokens so far, chunk count); a
        queued request ships prompt + metadata only. Ownership LEAVES
        this engine with the payload (slots released and frozen, queue
        entries removed), so a request can never be served by two
        workers at once — exactly-once by construction. Must be called
        at a chunk boundary (between steps): the carry and the host
        token buffers agree only there. The payload travels as one npz
        blob under a sha256 digest; :meth:`absorb_rows` verifies it
        end-to-end (the chunked RPC channel additionally verifies per
        part in transit). Unknown ids are refused before anything is
        touched."""
        import jax

        from paddle_tpu.distributed.checkpoint import _np_storable
        if self._spec_configured:
            raise ValueError(
                "speculative serving does not migrate rows yet: the "
                "draft cache / pending-token carry is not in the "
                "migration payload; serve without draft_model= to "
                "migrate")
        if any(m is not None for m in self._ring_meta):
            raise RuntimeError(
                "extract_rows() with staged-but-unscattered admission "
                "ring rows: run one more step() so the pending ring "
                "splice lands in the carry first")
        want = [int(i) for i in request_ids]
        by_slot = {int(s.request.id): (i, s)
                   for i, s in self.scheduler.slots.occupied()}
        queued_ids = {int(r.id) for r in self.scheduler.queued()}
        unknown = [i for i in want
                   if i not in by_slot and i not in queued_ids]
        if unknown:
            raise ValueError(
                f"extract_rows: request ids {unknown} are neither in a "
                f"slot nor queued on this engine (already finished, or "
                f"never submitted here)")
        inflight = [(rid,) + by_slot[rid] for rid in want
                    if rid in by_slot]
        rows = [slot_idx for _, slot_idx, _ in inflight]
        arrays: Dict[str, np.ndarray] = {}
        leaf_meta: Dict[str, Any] = {"kc": [], "vc": []}
        st = self.state
        if rows:
            idx = np.asarray(rows, np.int64)

            def gather_cache(name, tree):
                leaves, _ = jax.tree_util.tree_flatten(tree)
                for i, leaf in enumerate(leaves):
                    a = np.asarray(jax.device_get(leaf))
                    # the put_cache batch-axis rule: ndim-4
                    store, tag = _np_storable(
                        np.take(a, idx, axis=a.ndim - 4))
                    arrays[f"{name}_leaf_{i}"] = store
                    leaf_meta[name].append({"dtype": tag})

            gather_cache("kc", st.kc)
            gather_cache("vc", st.vc)
            for nm, leaf in (("logits", st.logits), ("pos", st.pos),
                             ("keys", st.keys), ("eos", st.eos),
                             ("temp", st.temp)):
                store, tag = _np_storable(
                    np.take(np.asarray(jax.device_get(leaf)), idx,
                            axis=0))
                arrays[nm] = store
                leaf_meta[nm] = {"dtype": tag}
        now = time.monotonic()
        slots_meta = []
        for j, (rid, slot_idx, slot) in enumerate(inflight):
            arrays[f"row{j}_prompt"] = np.asarray(slot.request.prompt)
            for p, piece in enumerate(slot.tokens):
                arrays[f"row{j}_piece{p}"] = np.asarray(piece)
            slots_meta.append({"row": j,
                               "request": self._req_meta(slot.request,
                                                         now),
                               "pieces": len(slot.tokens),
                               "chunks": slot.chunks})
        queue_meta = []
        for j, req in enumerate(self.scheduler.remove(
                [rid for rid in want if rid not in by_slot])):
            arrays[f"queue{j}_prompt"] = np.asarray(req.prompt)
            queue_meta.append(self._req_meta(req, now))
        # ownership leaves with the payload: release + freeze the
        # donated rows so the next step neither serves nor re-emits them
        for rid, slot_idx, slot in inflight:
            if slot.pinned_slab is not None:
                self.prefix_cache.unpin(slot.pinned_slab)
                slot.pinned_slab = None
            self.scheduler.slots.release(slot_idx)
        if rows:
            self._freeze_rows(rows)
        self._g_qdepth.set(len(self.scheduler))
        self._mark_between_steps()
        meta = {
            "kind": "paddle_tpu.row_migration", "version": 1,
            "rows": len(inflight), "quant": self._b.quant,
            "mesh_axes": (dict(self._b.sharding.axes)
                          if self._b.sharding is not None else None),
            "leaves": leaf_meta, "slots": slots_meta,
            "queue": queue_meta,
        }
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        payload = buf.getvalue()
        self._c_migrated_out.inc(len(want))
        obs.tracer.event("serving.migrate.extract",
                         in_flight=len(inflight),
                         queued=len(queue_meta))
        return {"kind": meta["kind"], "meta": meta, "data": payload,
                "sha256": hashlib.sha256(payload).hexdigest()}

    def absorb_rows(self, payload: Dict[str, Any]) -> Dict[int, int]:
        """The destination side of a live migration: verify the payload
        digest (typed ``SlabTransferError`` on a flipped bit),
        cross-check quant recipe (``QuantMismatchError``) and mesh
        topology (``MeshMismatchError``), then scatter each shipped
        carry row into a free slot through the SAME fused admission
        scatter a prefill uses — a row-remapped restore, one row at a
        time, into a LIVE engine. The shipped row keeps its in-flight
        RNG key, so a sampled stream CONTINUES exactly where the source
        left it (no re-derivation); greedy continuation is bit-exact by
        the same argument as restore. Shipped queued requests re-enter
        this engine's queue. Every absorbed request gets a fresh engine
        id; returns ``{source engine id: new engine id}`` — the cluster
        frontend rewires its assignment table through it."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.distributed.checkpoint import _np_restore
        from paddle_tpu.inference.sharding import MeshMismatchError
        from paddle_tpu.runtime.resilience import SlabTransferError
        if self._spec_configured:
            raise ValueError(
                "speculative serving does not migrate rows yet: absorb "
                "into an engine built without draft_model=")
        if payload.get("kind") != "paddle_tpu.row_migration":
            raise ValueError(
                f"absorb_rows: payload kind {payload.get('kind')!r} is "
                f"not a row-migration payload")
        raw = payload["data"]
        got = hashlib.sha256(raw).hexdigest()
        want = payload.get("sha256", "")
        if got != want:
            raise SlabTransferError(
                f"migration payload is corrupt: sha256 {got[:16]}… != "
                f"{want[:16]}… — refusing to scatter corrupt rows into "
                f"a live carry", key="row_migration")
        meta = payload["meta"]
        if meta.get("quant") != self._b.quant:
            from paddle_tpu.quantization.kv_cache import \
                QuantMismatchError
            raise QuantMismatchError(
                f"migration payload carries quant recipe "
                f"{meta.get('quant') or 'none'!r} but this engine's "
                f"backend serves {self._b.quant or 'none'!r}")
        have_axes = (dict(self._b.sharding.axes)
                     if self._b.sharding is not None else None)
        if meta.get("mesh_axes") != have_axes:
            raise MeshMismatchError(
                f"migration payload recorded mesh "
                f"{meta.get('mesh_axes')} but this engine serves "
                f"{have_axes}")
        n = int(meta["rows"])
        free = self.scheduler.slots.free_slots()
        if len(free) < n:
            raise RuntimeError(
                f"absorb_rows needs {n} free slots, this engine has "
                f"{len(free)} — migrate to a less-loaded worker")
        npz = np.load(io.BytesIO(raw), allow_pickle=False)
        now = time.monotonic()
        mapping: Dict[int, int] = {}
        if n:
            lm = meta["leaves"]

            def cache_tree(name, template):
                tl, treedef = jax.tree_util.tree_flatten(template)
                recorded = lm[name]
                if len(recorded) != len(tl):
                    raise SlabTransferError(
                        f"migration payload cache layout mismatch: "
                        f"{len(recorded)} {name} leaves recorded, "
                        f"backend expects {len(tl)}", key=name)
                return jax.tree_util.tree_unflatten(
                    treedef,
                    [jnp.asarray(_np_restore(npz[f"{name}_leaf_{i}"],
                                             m["dtype"]))
                     for i, m in enumerate(recorded)])

            kc1 = cache_tree("kc", self.state.kc)
            vc1 = cache_tree("vc", self.state.vc)
            logits1 = jnp.asarray(
                _np_restore(npz["logits"], lm["logits"]["dtype"]))
            pos1 = _np_restore(npz["pos"], lm["pos"]["dtype"])
            keys1 = _np_restore(npz["keys"], lm["keys"]["dtype"])
            eos1 = _np_restore(npz["eos"], lm["eos"]["dtype"])
            temp1 = _np_restore(npz["temp"], lm["temp"]["dtype"])
            for sm in meta["slots"]:
                j = int(sm["row"])
                req = self._req_from_meta(sm["request"],
                                          npz[f"row{j}_prompt"], now)
                old_id = int(req.id)
                req.id = self._next_id
                self._next_id += 1
                slot_idx = self.scheduler.slots.occupy(req)
                # raw-key scatter: bypass _scatter's key derivation —
                # the shipped key IS the row's live stream state
                st = self.state
                aidx1 = None
                if st.adapter_idx is not None:
                    aidx1 = jnp.asarray(
                        0 if (req.adapter is None
                              or self.adapter_store is None)
                        else self.adapter_store.index(req.adapter),
                        jnp.int32)
                (logits, kc, vc, pos, keys, done, eos, temp, aidx) = \
                    self._admit_fn(
                        st.logits, st.kc, st.vc, st.pos, st.keys,
                        st.done, st.eos, st.temp, st.adapter_idx,
                        logits1, kc1, vc1,
                        jnp.asarray(slot_idx, jnp.int32),
                        jnp.asarray(j, jnp.int32),
                        jnp.asarray(pos1[j], jnp.int32),
                        jnp.asarray(keys1[j], jnp.uint32),
                        jnp.asarray(eos1[j], jnp.int32),
                        jnp.asarray(temp1[j], jnp.float32), aidx1)
                self.state = dataclasses.replace(
                    st, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
                    done=done, eos=eos, temp=temp, adapter_idx=aidx)
                slot = self.scheduler.slots.entries[slot_idx]
                slot.admitted_at = now
                if req.adapter is not None \
                        and self.adapter_store is not None:
                    slot.adapter_rev = \
                        self.adapter_store.revision(req.adapter)
                slot.chunks = int(sm["chunks"])
                slot.tokens = [np.asarray(npz[f"row{j}_piece{p}"])
                               for p in range(int(sm["pieces"]))]
                slot.kv_pos = len(req.prompt) + sum(
                    len(t) for t in slot.tokens)
                mapping[old_id] = req.id
        for j, qm in enumerate(meta["queue"]):
            req = self._req_from_meta(qm, npz[f"queue{j}_prompt"], now)
            old_id = int(req.id)
            req.id = self._next_id
            self._next_id += 1
            self.scheduler.push(req)
            mapping[old_id] = req.id
        self._g_qdepth.set(len(self.scheduler))
        self._mark_between_steps()
        self._c_migrated_in.inc(len(mapping))
        obs.tracer.event("serving.migrate.absorb", in_flight=n,
                         queued=len(meta["queue"]))
        return mapping

    # -- disaggregated prefill/decode (serving/cluster) --------------------
    def prefill_extract(self, prompt) -> Dict[str, Any]:
        """The PREFILL-pool side of disaggregated serving: run ONE
        admission prefill for ``prompt`` outside the slot table and
        return its row state — the bucketed KV rows plus the resume
        logits — as a serializable prefix-slab payload (host numpy
        pytrees, dtype-tagged by the backend's quant recipe). A decode
        engine admits the shipped payload via :meth:`load_prefix_slab`;
        the prompt then resolves as a FULL prefix hit whose admission is
        the one-row scatter alone, bit-exact with a local cold
        admission (the slab rows ARE the cold prefill's row state).
        Counts on this engine's ``prefill_dispatches`` ledger — the
        per-pool accounting the cluster bench asserts on."""
        import jax

        if self._b.latent_layers:
            from paddle_tpu.inference.generate import LatentCacheError
            raise LatentCacheError(
                "a prefix slab is a prompt's keys and values per head; a "
                "model with latent attention caches a latent and a rope "
                "key: no slab can be cut from its caches")
        if self._b.has_windows:
            from paddle_tpu.inference.generate import WindowedModelError
            raise WindowedModelError(
                "a prefix slab is a prompt's cache rows by position; a "
                "model with windowed layers keeps a position at position "
                "% window: no slab can be cut from its caches")
        prompt = np.asarray(prompt)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1:
            raise ValueError(
                f"prefill_extract takes one (S,) prompt, got shape "
                f"{prompt.shape}")
        S = len(prompt)
        bucket = self.scheduler.bucket(S)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :S] = prompt
        logitsN, kcN, vcN = self._b.admit_prefill(
            ids, np.asarray([S], np.int32), np.asarray([0], np.int32))
        self._c_prefill.inc()
        ops = self._slab_ops
        if ops is None:
            from paddle_tpu.serving.prefix_cache import SlabOps
            ops = self._slab_ops = SlabOps(self._b.sharding,
                                           self._b.head_major)
        skc, svc, slg = ops.extract(kcN, vcN, logitsN, 0, bucket)

        def host(t):
            return jax.tree_util.tree_map(
                lambda a: np.asarray(jax.device_get(a)), t)

        return {"prompt": prompt, "bucket": int(bucket),
                "kc": host(skc), "vc": host(svc),
                "logits": host(slg), "quant": self._b.quant}

    def load_prefix_slab(self, payload: Dict[str, Any]):
        """The DECODE-pool side: admit a :meth:`prefill_extract` payload
        into this engine's prefix cache. The next ``submit`` of the same
        prompt admits as a full hit — zero prefill dispatches on the
        decode pool. A quant-recipe mismatch between the pools is
        refused typed (``QuantMismatchError``): int8 KV rows scattered
        into an fp32 carry would decode garbage silently."""
        import jax
        import jax.numpy as jnp
        if self.prefix_cache is None:
            raise ValueError(
                "load_prefix_slab needs the prefix cache enabled: the "
                "shipped slab admits through the full-hit path")
        if payload.get("quant") != self._b.quant:
            from paddle_tpu.quantization.kv_cache import QuantMismatchError
            raise QuantMismatchError(
                f"shipped slab carries quant recipe "
                f"{payload.get('quant') or 'none'!r} but this engine's "
                f"backend serves {self._b.quant or 'none'!r}")

        def dev(t):
            return jax.tree_util.tree_map(jnp.asarray, t)

        return self.prefix_cache.insert(
            np.asarray(payload["prompt"]), dev(payload["kc"]),
            dev(payload["vc"]), dev(payload["logits"]),
            int(payload["bucket"]))

    # -- internals ---------------------------------------------------------
    def _admit_all(self, admitted, now: float) -> None:
        """One admission round. Per request: consult the prefix cache —
        a FULL hit admits via the fused row-scatter alone (ZERO prefill
        dispatches; the slab's logits + KV rows ARE the cold prefill's
        row state, so tokens stay bit-exact), a PARTIAL hit prefills
        only the uncached suffix on top of the loaded slab, a miss runs
        the cold prefill and populates the cache on the way through.
        Requests that do need a prefill are grouped by padded bucket
        width; with ``batch_admission`` each group runs as ONE batched
        dispatch (mixed cold/suffix rows — per-row pos0 keeps them
        independent).

        With the device admission ring active this whole round routes
        through :meth:`_admit_all_ring` instead: prefills stage their
        row state into device ring rows and the NEXT chunk program
        splices them in — zero host scatters, zero extra dispatch
        boundaries."""
        store = self.adapter_store
        if store is not None and store.version != self._b.lora_version:
            # a staged hot-swap hasn't applied yet (in-flight rows pin
            # the old revision): requests naming a PENDING adapter
            # revision wait at their tier's head rather than decode
            # through stacks that aren't theirs
            keep = []
            for slot_idx, req in admitted:
                if req.adapter is not None and \
                        self._served_rev.get(req.adapter) \
                        != store.revision(req.adapter):
                    self.scheduler.slots.release(slot_idx)
                    self.scheduler.push_front(req)
                else:
                    keep.append((slot_idx, req))
            admitted = keep
            if not admitted:
                return
        if self._ring_slots:
            self._admit_all_ring(admitted, now)
            return
        cache = self.prefix_cache
        plans = []
        for slot_idx, req in admitted:
            t0 = time.monotonic()
            S = len(req.prompt)
            hit = None
            if cache is not None:
                hit = cache.lookup(req.prompt,
                                   allow_partial=self._b.admit_pos0,
                                   adapter=self._adapter_tag(req.adapter))
            if hit is not None and hit.kind == "full":
                cache.pin(hit.slab)
                self._scatter(slot_idx, req, hit.slab.logits,
                              hit.slab.kc, hit.slab.vc, src=0, pos1=S)
                self._note_admit(slot_idx, req, now, t0, "full",
                                 tokens_saved=S, dispatches=0,
                                 slab=hit.slab, events=[])
                self._c_disp_saved.inc()
                continue
            plans.append((slot_idx, req, hit))
        groups: Dict[int, list] = {}
        for slot_idx, req, hit in plans:
            cached = (hit.cached_len
                      if hit is not None and hit.kind == "partial" else 0)
            w = self.scheduler.bucket(len(req.prompt) - cached)
            groups.setdefault(w, []).append((slot_idx, req, hit, cached))
        for w, grp in sorted(groups.items()):
            if self.batch_admission and self._b.admit_batch_any \
                    and len(grp) > 1:
                self._admit_group(w, grp, now)
            else:
                for item in grp:
                    self._admit_group(w, [item], now)
        self._prefix_sync()

    def _admit_all_ring(self, admitted, now: float) -> None:
        """Ring admission round: pick a free device ring row per
        admitted request, run one ring-staged prefill dispatch per
        bucket group (one TOTAL per group with ``batch_admission``), and
        record the per-row splice metadata (destination slot, resume
        pos, row key, eos, temp) the next chunk's prologue consumes.
        Admissions beyond the ring's free rows are UN-ADMITTED — slot
        released, request re-queued at its tier's head with its original
        submit_time (``ring_full`` backpressure) — and retry next step
        once the chunk has drained the ring."""
        import collections
        free = collections.deque(
            r for r, m in enumerate(self._ring_meta) if m is None)
        if len(admitted) > len(free):
            keep, spill = admitted[:len(free)], admitted[len(free):]
            for slot_idx, req in reversed(spill):
                self.scheduler.slots.release(slot_idx)
                self.scheduler.push_front(req)
                self._c_ring_full.inc()
                obs.tracer.event("serving.admission.ring_full",
                                 request=req.id,
                                 ring_slots=self._ring_slots)
            admitted = keep
            self._g_qdepth.set(len(self.scheduler))
        groups: Dict[int, list] = {}
        for slot_idx, req in admitted:
            w = self.scheduler.bucket(len(req.prompt))
            groups.setdefault(w, []).append((slot_idx, req))
        try:
            for w, grp in sorted(groups.items()):
                if self.batch_admission and len(grp) > 1:
                    self._admit_group_ring(w, grp, free, now)
                else:
                    for item in grp:
                        self._admit_group_ring(w, [item], free, now)
        except Exception:
            self._admission_failed(admitted)
            raise

    def _row_key(self, req: Request):
        """The admitted row's RNG key. By default the SAME rule as
        ``generate(chunk_size=)`` at B=1: the request's stream is keyed
        by its seed alone. Under ``request_keyed_rng`` the request-keyed
        stream: a requeued row that replays T teacher-forced tokens
        resumes at the key the undisturbed row would hold after T
        advances (sampled replay parity)."""
        import jax.random as jrandom
        if self.request_keyed_rng:
            rng_id = (req.rng_request_id if req.rng_request_id is not None
                      else req.id)
            return derive_row_key(req.seed, rng_id, req.rng_tokens_emitted)
        return jrandom.split(jrandom.PRNGKey(req.seed), 1)[0]

    def _pack_group(self, w: int, items):
        """The host arrays of one admission-prefill dispatch over
        ``items`` = ``(request, cached)`` pairs of one bucket width
        ``w``: ids right-padded past each prompt's uncached suffix,
        per-row true lengths and cache offsets, and the rows' adapter
        indices (``None`` without an adapter store)."""
        N = len(items)
        ids = np.zeros((N, w), np.int32)
        true_len = np.zeros((N,), np.int32)
        pos0 = np.zeros((N,), np.int32)
        for j, (req, cached) in enumerate(items):
            suffix = np.asarray(req.prompt)[cached:]
            ids[j, :len(suffix)] = suffix
            true_len[j] = len(suffix)
            pos0[j] = cached
        aidxN = None
        if self.adapter_store is not None:
            aidxN = np.asarray([self.adapter_store.index(req.adapter)
                                for req, _ in items], np.int32)
        return ids, true_len, pos0, aidxN

    def _count_prefill(self, w: int, true_len) -> None:
        """What an admission prefill of bucket ``w`` NEEDED, from its
        rows' true lengths, by bucket: the rows, their positions and the
        (query, key) pairs of causal attention over them — the padded
        tail of the bucket is none of them (and EVA's own counts,
        ``_count_eva_prefill``)."""
        acc = self._prefill_need.setdefault(int(w), dict.fromkeys(
            ("rows", "positions", "causal_pairs"), 0))
        for n in map(int, true_len):
            acc["rows"] += 1
            acc["positions"] += n
            acc["causal_pairs"] += n * (n + 1) // 2
        self._count_eva_prefill(w, true_len)

    def _count_eva_prefill(self, w: int, true_len) -> None:
        """What an EVA admission prefill of bucket ``w`` NEEDED, from its
        rows' true lengths: the aligned windows they reach, and by bucket
        the rows, their positions and the (query, key) pairs of the
        windows' causal halves and (query, summary) pairs of the windows
        before — the padded tail of the bucket is none of them."""
        if self._b.eva is None:
            return
        W, C = self._b.eva
        acc = self._eva_prefill.setdefault(int(w), dict.fromkeys(
            ("rows", "positions", "local_pairs", "summary_pairs"), 0))
        for n in map(int, true_len):
            full, r = divmod(n, W)
            self._c_eva_prefill.inc(full + (r > 0))
            acc["rows"] += 1
            acc["positions"] += n
            acc["local_pairs"] += (full * W * (W + 1) + r * (r + 1)) // 2
            acc["summary_pairs"] += (W // C) * (
                W * full * (full - 1) // 2 + r * full)

    def _admit_group_ring(self, w: int, grp, free, now: float) -> None:
        """ONE ring-staged admission-prefill dispatch for the group
        (plus one draft-cache staging dispatch under speculation): the
        freshly prefilled rows land in device ring rows, never on the
        host."""
        t0 = time.monotonic()
        N = len(grp)
        rows = [free.popleft() for _ in range(N)]
        ids, true_len, pos0, aidxN = self._pack_group(
            w, [(req, 0) for _, req in grp])
        ev0 = self._b.event_count()
        with TraceAnnotation("serving.admit.prefill_enqueue"):
            self._b.ring_admit(ids, true_len, pos0, rows, aidx=aidxN)
            self._timeline.fed("prefill")
            self._c_prefill.inc()
            self._count_prefill(w, true_len)
            if self._spec_active:
                self._b.ring_admit_draft(ids, rows)
                self._c_draft_prefill.inc()
        if N > 1:
            self._c_batched_groups.inc()
            self._c_disp_saved.inc(N - 1)
        events = self._b.events_since(ev0)
        for j, (slot_idx, req) in enumerate(grp):
            # the key comes back to the host for the ring's splice
            # arrays, and the readback waits out the prefill enqueued
            # above: device time inside admit, kept apart as admit_wait
            with obs.phase("serving.admit.row_key",
                           self._h_phase["admit_wait"]) as read:
                key1 = np.asarray(self._row_key(req))
            self._timeline.drained(read.t1)
            self._ring_meta[rows[j]] = {
                "slot": slot_idx, "pos": len(req.prompt),
                "key": np.asarray(key1, np.uint32),
                "eos": (-1 if req.eos_token_id is None
                        else int(req.eos_token_id)),
                "temp": float(req.temperature),
                "aidx": (0 if aidxN is None else int(aidxN[j])),
                "spec_on": (req.speculative
                            if req.speculative is not None else True)}
            self._c_ring_staged.inc()
            self._note_admit(slot_idx, req, now, t0, "miss",
                             tokens_saved=0,
                             dispatches=1 if j == 0 else 0,
                             slab=None, events=events)

    def _ring_args(self) -> Tuple[tuple, int]:
        """Host-side splice arrays for the chunk program's ring
        prologue: per-ring-row destination slot (-1 = empty, dropped on
        device), resume pos, row key, eos, temp. Returns ``(arrays,
        staged_count)``."""
        R = self._ring_slots
        slot = np.full((R,), -1, np.int32)
        pos = np.zeros((R,), np.int32)
        keys = np.zeros((R, 2), np.uint32)
        eos = np.full((R,), -1, np.int32)
        temp = np.ones((R,), np.float32)
        aidx = (np.zeros((R,), np.int32)
                if self.adapter_store is not None else None)
        son = (np.ones((R,), np.bool_)
               if self._spec_configured else None)
        n = 0
        for r, m in enumerate(self._ring_meta):
            if m is None:
                continue
            slot[r] = m["slot"]
            pos[r] = m["pos"]
            keys[r] = m["key"]
            eos[r] = m["eos"]
            temp[r] = m["temp"]
            if aidx is not None:
                aidx[r] = m.get("aidx", 0)
            if son is not None:
                son[r] = m.get("spec_on", True)
            n += 1
        return (slot, pos, keys, eos, temp, aidx, son), n

    def _admission_failed(self, admitted) -> None:
        """A ring prefill of this round raised. No request may stay in
        a slot without a row: those the round had not staged yet go
        back to their tier's head (slot released, original submit_time,
        as under ``ring_full``). Where the failed program had taken the
        donated ring, the rows staged in it went with it: a new, empty
        ring is built and their requests go back too — a chunk must
        never splice rows of zeros under their positions."""
        entries = self.scheduler.slots.entries
        staged = [m["slot"] for m in self._ring_meta if m is not None]
        back = [(i, req) for i, req in admitted if i not in staged]
        if self._b.ring_consumed():
            back = [(i, entries[i].request) for i in staged] + back
            self._ring_meta = [None] * self._ring_slots
            self._b.ring_init(self._ring_slots)
        for slot_idx, req in reversed(back):
            self.scheduler.slots.release(slot_idx)
            self.scheduler.push_front(req)
        self._g_qdepth.set(len(self.scheduler))

    def _ring_drained(self, n: Optional[int]) -> None:
        """A chunk program's ring prologue ran: the staged rows are in
        the carry now — clear the metadata and credit the scatter."""
        if not n:
            return
        self._ring_meta = [None] * self._ring_slots
        self._c_ring_scattered.inc(n)

    def _admit_group(self, w: int, grp, now: float) -> None:
        """ONE admission-prefill dispatch for the group: batch-N padded
        suffix ids, per-row true lengths and cache offsets, caches
        preloaded with each partial row's slab; then one fused
        row-scatter per admitted request, and — cache enabled — one
        slab extraction per newly seen prompt."""
        cache, ops = self.prefix_cache, self._slab_ops
        t0 = time.monotonic()
        N = len(grp)
        ids, true_len, pos0, aidxN = self._pack_group(
            w, [(req, cached) for _, req, _, cached in grp])
        kcN = vcN = None
        for j, (slot_idx, req, hit, cached) in enumerate(grp):
            if cached:
                cache.pin(hit.slab)
                if kcN is None:
                    kcN, vcN = self._b.empty_cache(N)
                kcN, vcN = ops.load(kcN, vcN, hit.slab.kc, hit.slab.vc,
                                    j)
        ev0 = self._b.event_count()
        with TraceAnnotation("serving.admit.prefill_enqueue"):
            logitsN, kcN, vcN = self._b.admit_prefill(
                ids, true_len, pos0, kcN, vcN, aidx=aidxN)
            self._timeline.fed("prefill")
        self._c_prefill.inc()
        self._count_prefill(w, true_len)
        if N > 1:
            self._c_batched_groups.inc()
            self._c_disp_saved.inc(N - 1)
        events = self._b.events_since(ev0)
        for j, (slot_idx, req, hit, cached) in enumerate(grp):
            S = len(req.prompt)
            self._scatter(slot_idx, req, logitsN, kcN, vcN, src=j,
                          pos1=S)
            if cache is not None:
                digests = hit.digests if hit is not None else None
                if digests is None or not cache.contains_full(digests):
                    bucket = self.scheduler.bucket(S)
                    skc, svc, slg = ops.extract(kcN, vcN, logitsN, j,
                                                bucket)
                    cache.insert(req.prompt, skc, svc, slg, bucket,
                                 digests=digests,
                                 adapter=self._adapter_tag(req.adapter))
            cls = "partial" if cached else "miss"
            self._note_admit(slot_idx, req, now, t0, cls,
                             tokens_saved=cached,
                             dispatches=1 if j == 0 else 0,
                             slab=hit.slab if cached else None,
                             events=events)

    def _scatter(self, slot_idx: int, req: Request, logits1, kc1, vc1,
                 src: int, pos1: int) -> None:
        """The fused admission row-scatter: row ``src`` of the given
        row state lands in carry row ``slot_idx``. A full-prefix hit's
        WHOLE admission is one of these. This is the LEGACY host-side
        admission (prefix-cache and bundle backends); ring-served
        engines never reach it (``admission.host_scattered`` stays 0)."""
        import jax.numpy as jnp

        self._c_host_scattered.inc()
        key1 = jnp.asarray(self._row_key(req), jnp.uint32)
        st = self.state
        aidx1 = None
        if st.adapter_idx is not None:
            aidx1 = jnp.asarray(
                0 if self.adapter_store is None
                else self.adapter_store.index(req.adapter), jnp.int32)
        (logits, kc, vc, pos, keys, done, eos, temp,
         aidx) = self._admit_fn(
            st.logits, st.kc, st.vc, st.pos, st.keys, st.done, st.eos,
            st.temp, st.adapter_idx, logits1, kc1, vc1,
            jnp.asarray(slot_idx, jnp.int32), jnp.asarray(src, jnp.int32),
            jnp.asarray(pos1, jnp.int32), key1,
            jnp.asarray(-1 if req.eos_token_id is None
                        else int(req.eos_token_id), jnp.int32),
            jnp.asarray(req.temperature, jnp.float32), aidx1)
        self.state = dataclasses.replace(
            st, logits=logits, kc=kc, vc=vc, pos=pos, keys=keys,
            done=done, eos=eos, temp=temp, adapter_idx=aidx)

    def _note_admit(self, slot_idx: int, req: Request, now: float,
                    t0: float, cls: str, tokens_saved: int,
                    dispatches: int, slab, events) -> None:
        slot = self.scheduler.slots.entries[slot_idx]
        slot.admitted_at = now
        slot.events.extend(events)
        slot.streamed = 0
        if self.adapter_store is not None:
            slot.adapter_rev = (
                None if req.adapter is None
                else self.adapter_store.revision(req.adapter))
            self._adapter_row_counter(req.adapter or "base").inc()
        enabled = self.prefix_cache is not None
        slot.prefix_hit = cls if enabled else None
        slot.prefill_tokens_saved = int(tokens_saved)
        slot.admission_dispatches = int(dispatches)
        slot.pinned_slab = slab
        self._h_admit[cls].observe(time.monotonic() - t0)
        if enabled:
            self._c_prefix[cls].inc()
            if tokens_saved:
                self._c_tokens_saved.inc(int(tokens_saved))
        self._h_qdelay.observe(now - req.submit_time)
        obs.tracer.event("serving.request.admitted", request=req.id,
                         slot=slot_idx,
                         queue_delay_s=round(now - req.submit_time, 6),
                         prefix_hit=slot.prefix_hit,
                         prefill_tokens_saved=int(tokens_saved))

    def _prefix_sync(self) -> None:
        """Mirror the cache's pool-level numbers into the engine's typed
        registry (gauges absolute; insertion/eviction counters by delta,
        so a SHARED cache's events land once per engine observation)."""
        cache = self.prefix_cache
        if cache is None:
            return
        st = cache.stats()
        self._g_prefix_bytes.set(st["bytes_cached"])
        self._g_prefix_slabs.set(st["slabs"])
        last = self._last_prefix_stats
        for key, ctr in (("insertions", self._c_prefix_insert),
                         ("evictions", self._c_prefix_evict)):
            if st[key] > last[key]:
                ctr.inc(st[key] - last[key])
                last[key] = st[key]

    def _dispatch_chunk(self, occupied) -> np.ndarray:
        """The chunk through the degradation ladder (speculative ->
        chunked -> per-token), entered in the step's ``dispatch`` phase:
        each rung switches to ``wait`` once its enqueue has returned and
        the host blocks on the tokens, and a rung that takes over from a
        failed one goes back to ``dispatch``."""
        from paddle_tpu.flags import flags as _flags
        from paddle_tpu.runtime.resilience import (
            DecodeFailedError, DegradationEvent, classify_error,
            fault_injector, record_event)

        self._last_nv = None
        ring, n_staged = (self._ring_args() if self._ring_slots
                          else (None, None))
        degr: list = []
        ev0 = self._b.event_count()
        if self._spec_active:
            try:
                if self.replica_tag:
                    fault_injector.on_call(
                        f"serving.{self.replica_tag}.chunk")
                toks, nv, self.state = self._b.decode_chunk_spec(
                    self.state, self.chunk_size, ring, K=self._k_now)
                self._timeline.fed("chunk")
                self._c_chunk.inc()
                self._c_slot_steps.inc(self.num_slots * self.chunk_size)
                self._ring_drained(n_staged)
                self._note_events(occupied, ev0, [])
                self._phase_to("wait")
                self._last_nv = np.asarray(jax.device_get(nv))
                return np.asarray(toks)
            except Exception as e:
                if classify_error(e) != "transient":
                    self._harvest_before_raise(e, "serving.chunk_fatal")
                    raise
                if not _flags.resilience_auto_degrade:
                    err = DecodeFailedError(
                        f"serving speculative chunk dispatch failed "
                        f"with auto-degrade off: {str(e)[:300]}",
                        events=self._b.events_since(ev0), last_error=e)
                    self._harvest_before_raise(
                        e, "serving.chunk_failed_no_rung")
                    raise err from e
                # speculative -> chunked demotion (one-way): one counted
                # masked forward (decode.spec_demote) commits each row's
                # pending token, the draft carry is dropped, and the
                # plain ring chunk below serves the SAME state — no
                # in-flight request is lost, the engine keeps serving at
                # 1 token/step instead of dying. Admissions stop staging
                # draft caches; per-slot acceptance stats freeze at the
                # last successful speculative chunk.
                ev = DegradationEvent(
                    site="serve.chunk", from_level="speculative",
                    to_level="chunked", error_class=type(e).__name__,
                    error=str(e)[:300])
                record_event(ev)
                self._c_degr.inc()
                degr.append(ev)
                self._phase_to("dispatch")
                self.state = self._b.spec_demote(self.state)
                self._spec_active = False
        try:
            if self.replica_tag:
                # the per-replica fault site: a plan targeting
                # "serving.<tag>.chunk" kills/hangs THIS replica while
                # its ReplicaSet peers (different tags) keep serving
                fault_injector.on_call(
                    f"serving.{self.replica_tag}.chunk")
            toks, self.state = self._b.decode(self.state, self.chunk_size,
                                              ring)
            self._timeline.fed("chunk")
            self._c_chunk.inc()
            self._c_slot_steps.inc(self.num_slots * self.chunk_size)
            self._ring_drained(n_staged)
            self._note_events(occupied, ev0, degr)
            self._phase_to("wait")
            return np.asarray(toks)
        except Exception as e:
            # the chunk program is given the carry (donated): a dispatch
            # that failed after it had taken it leaves no state for the
            # per-token rung — or for the next step — to re-enter
            gone = self.state.consumed
            if classify_error(e) != "transient" and not gone:
                # fatal: the router's breaker counts this. Harvest rows
                # whose HOST tokens already finish them and dump the
                # postmortem before the error propagates — a finished
                # request must never ride down with the batch
                self._harvest_before_raise(e, "serving.chunk_fatal")
                raise
            if (gone or not _flags.resilience_auto_degrade
                    or not self._b.has_step_rung()):
                why, reason = (
                    ("after it had consumed the carry",
                     "serving.carry_consumed") if gone else
                    ("with no per-token rung available",
                     "serving.chunk_failed_no_rung"))
                err = DecodeFailedError(
                    f"serving chunk dispatch failed {why}: "
                    f"{str(e)[:300]}",
                    events=self._b.events_since(ev0) + degr,
                    last_error=e)
                self._harvest_before_raise(e, reason)
                raise err from e
            ev = DegradationEvent(
                site="serve.chunk", from_level="chunked",
                to_level="per_token", error_class=type(e).__name__,
                error=str(e)[:300])
            record_event(ev)
            self._c_degr.inc()
            degr.append(ev)
        # per-token rung: T single-step dispatches on the SAME carry —
        # the failed chunk never took it (an injected fault fires before
        # the dispatch; a dispatch that did take its donated carry was
        # turned into DecodeFailedError above), so every admitted
        # request rides through the degradation. Each step consumes the
        # carry it is given and hands back the next. The FIRST step
        # carries the pending ring splice; later steps pass an empty
        # ring (same compiled program, all rows dropped).
        parts = []
        try:
            for s in range(self.chunk_size):
                self._phase_to("dispatch")
                if self.replica_tag:
                    fault_injector.on_call(
                        f"serving.{self.replica_tag}.step")
                toks1, self.state = self._b.decode(self.state, 1, ring,
                                                   rung="step")
                self._timeline.fed("chunk")
                if s == 0 and n_staged:
                    self._ring_drained(n_staged)
                    ring, _ = self._ring_args()   # now empty
                self._c_step.inc()
                self._phase_to("wait")
                parts.append(np.asarray(toks1))
        except Exception as e2:
            # the ladder is exhausted mid-rung. Tokens from the steps
            # that DID run are real — the carry advanced — so absorb
            # them into the slot buffers first: requests they complete
            # are harvested below, and a router requeue replays them
            # instead of re-generating (no token is lost OR re-emitted)
            if parts:
                cols = np.concatenate(parts, axis=1)
                for i, slot in occupied:
                    slot.tokens.append(cols[i])
                    slot.chunks += 1
            err = DecodeFailedError(
                f"serving per-token rung failed after the chunk rung "
                f"degraded: {str(e2)[:300]}",
                events=self._b.events_since(ev0) + degr, last_error=e2)
            self._harvest_before_raise(e2, "serving.ladder_exhausted")
            raise err from e2
        self._c_slot_steps.inc(self.num_slots * self.chunk_size)
        self._note_events(occupied, ev0, degr)
        return np.concatenate(parts, axis=1)

    def _harvest_before_raise(self, error: BaseException,
                              reason: str) -> None:
        """The last act before a serving chunk error propagates: rows
        whose HOST-side token buffer already satisfies their finish
        condition (EOS collected in an earlier chunk / budget met by the
        absorbed rung steps) are harvested into ``_results`` — they are
        COMPLETE, bit-exact results and must not be lost with the batch
        — and the genuinely unfinished requests are recorded (id +
        tokens generated so far) in the flight-recorder postmortem, so a
        crash dump accounts for every accepted request."""
        harvested, lost = [], []
        for i, slot in self.scheduler.slots.occupied():
            req = slot.request
            seq = (np.concatenate(slot.tokens) if slot.tokens
                   else np.zeros((0,), np.int64))
            fin = False
            if req.eos_token_id is not None and seq.size:
                hit = seq == req.eos_token_id
                if hit.any():
                    seq = seq[:int(np.argmax(hit)) + 1]
                    fin = True
            if len(seq) >= req.max_new_tokens:
                seq = seq[:req.max_new_tokens]
                fin = True
            if fin:
                res = self._finish(slot, seq, i)
                self._results[req.id] = res
                harvested.append(req.id)
                if slot.pinned_slab is not None:
                    self.prefix_cache.unpin(slot.pinned_slab)
                    slot.pinned_slab = None
                self.scheduler.slots.release(i)
                try:
                    # best-effort freeze: the backend may be the thing
                    # that just died, and the harvest must never mask
                    # the original error (a fenced replica's carry is
                    # rebuilt at unfence anyway)
                    self._freeze_rows([i])
                except Exception:
                    pass
            else:
                lost.append({"request": req.id,
                             "prompt_len": int(len(req.prompt)),
                             "tokens_generated": int(seq.size),
                             "max_new_tokens": req.max_new_tokens,
                             "chunks": slot.chunks})
        obs.record_crash(
            reason, error=error,
            extra={"site": "serve.chunk", "replica": self.replica_tag,
                   "harvested_requests": harvested,
                   "lost_requests": lost})

    def _note_events(self, occupied, ev0: int, degradations) -> None:
        """Attribute THIS dispatch's retry/degradation events to every
        request that was riding it (and only those — a request admitted
        after an earlier degradation never inherits it)."""
        new = self._b.events_since(ev0) + list(degradations)
        for _, slot in occupied:
            slot.events.extend(new)

    def _finish(self, slot, seq: np.ndarray, slot_idx: int,
                deadline_expired: bool = False,
                corrupt_row: bool = False):
        from paddle_tpu.runtime.resilience import GenerateResult
        req = slot.request
        fin = time.monotonic()       # same clock as submit/admit stamps
        latency = fin - req.submit_time
        self._h_latency.observe(latency)
        self._c_done.inc()
        ttft = (slot.first_token_at - slot.admitted_at
                if slot.first_token_at is not None else None)
        n_tok = int(seq.shape[0])
        tpot = None
        if slot.first_token_at is not None and n_tok > 1:
            tpot = max(0.0, fin - slot.first_token_at) / (n_tok - 1)
            self._h_tpot.observe(tpot)
        slo = self._check_slo(req, ttft, latency)
        degr = [e for e in slot.events
                if getattr(e, "kind", "") == "degradation"]
        record = {
            "level": "per_token" if degr else "chunked",
            "requested_level": "chunked",
            "retries": sum(1 for e in slot.events
                           if getattr(e, "kind", "") == "retry"),
            "degradations": [e.as_dict() for e in degr],
            "events": [e.as_dict() for e in slot.events],
            "serving": {
                "queue_delay_s": slot.admitted_at - req.submit_time,
                "latency_s": latency,
                # from admission; first_token_s is the same instant
                # from submit (== queue_delay_s + ttft_s, one clock)
                "ttft_s": ttft,
                "first_token_s": (
                    None if slot.first_token_at is None
                    else slot.first_token_at - req.submit_time),
                "tpot_s": tpot,
                "chunks": slot.chunks,
                "slot": slot_idx,
                "latency_class": req.latency_class,
                "slo": slo,
                # prefix-cache accounting for THIS request: its hit
                # class (None = cache disabled), the prompt tokens whose
                # prefill it skipped, and how many prefill dispatches
                # its admission issued (0 = full hit or rode a batched
                # group's dispatch)
                "prefix_hit": slot.prefix_hit,
                "prefill_tokens_saved": slot.prefill_tokens_saved,
                "admission_dispatches": slot.admission_dispatches,
                # True when the row was frozen at a chunk boundary past
                # its deadline and returned PARTIAL (tokens so far, not
                # the full budget) — the caller must be able to tell a
                # deadline cut from a genuine EOS/budget finish
                "deadline_expired": bool(deadline_expired),
                # True when the finite guard cut this row: its logits
                # went NaN/Inf and the engine froze it alone, returning
                # the pre-corruption prefix
                "corrupt_row": bool(corrupt_row),
                # cumulative speculative accounting for THIS request,
                # summed across every chunk re-entry it rode through
                # (None = engine not speculative). A request finished
                # after a speculative->chunked demotion reports the
                # stats frozen at the last speculative chunk.
                "speculative": None if not self._spec_configured else {
                    "rounds": int(slot.spec_rounds),
                    "accepted_drafts": int(slot.spec_accepted),
                    "acceptance_len_mean": (
                        slot.spec_accepted / slot.spec_rounds
                        if slot.spec_rounds else 0.0),
                    "num_speculative_tokens": int(self._b.K),
                    "overflow_tokens": int(slot.spec_overflow),
                },
            },
        }
        # the request's lifetime span (submit -> finished) on the same
        # monotonic axis as the dispatch spans it contains
        obs.tracer.add_span(
            "serving.request", int(req.submit_time * 1e9),
            int(fin * 1e9), request=req.id, slot=slot_idx,
            chunks=slot.chunks, tokens=int(seq.shape[0]),
            queue_delay_s=round(record["serving"]["queue_delay_s"], 6),
            level=record["level"])
        obs.tracer.event("serving.request.finished", request=req.id,
                         latency_s=round(latency, 6))
        if req.adapter is not None:
            record["serving"]["adapter"] = req.adapter
            record["serving"]["adapter_rev"] = slot.adapter_rev
        cb = self._stream_cb.pop(req.id, None)
        if cb is not None:
            # the FINAL flush: whatever the finish-side trims left
            # beyond the last chunk flush, with the final=True marker
            # every streaming consumer keys its terminator on
            new = seq[slot.streamed:]
            if slot.streamed == 0 and len(new):
                self._stream_ttft_hist(req.latency_class).observe(
                    fin - slot.admitted_at)
            slot.streamed = int(len(seq))
            cb(req.id, np.asarray(new), True)
        out = np.concatenate([req.prompt,
                              seq.astype(req.prompt.dtype)])[None]
        return GenerateResult.wrap(out, record)

    def _check_slo(self, req: Request, ttft: Optional[float],
                   latency: float) -> Optional[dict]:
        """Evaluate the request against its SLO targets (per-request
        override, else the engine's per-class defaults). Bumps the
        per-class request/violation counters; returns the record block
        (None when the class has no targets at all)."""
        cls = req.latency_class
        defaults = self.slo_targets.get(cls, {})
        t_ttft = (req.slo_ttft_s if req.slo_ttft_s is not None
                  else defaults.get("ttft_s"))
        t_lat = (req.slo_latency_s if req.slo_latency_s is not None
                 else defaults.get("latency_s"))
        if t_ttft is None and t_lat is None:
            return None
        r = self.registry
        r.counter(f"serving.slo.{cls}.requests",
                  "requests finished in this latency class").inc()
        out = {"class": cls, "violated": False}
        if t_ttft is not None:
            out["ttft_target_s"] = t_ttft
            # a request that never produced a token has no TTFT: that IS
            # a violation, not a pass
            if ttft is None or ttft > t_ttft:
                out["violated"] = True
                out["ttft_violated"] = True
                r.counter(f"serving.slo.{cls}.ttft_violations",
                          "TTFT above the class/request target").inc()
        if t_lat is not None:
            out["latency_target_s"] = t_lat
            if latency > t_lat:
                out["violated"] = True
                out["latency_violated"] = True
                r.counter(f"serving.slo.{cls}.latency_violations",
                          "end-to-end latency above the class/request "
                          "target").inc()
        return out

    # -- observability -----------------------------------------------------
    def status(self) -> Dict[str, Any]:
        """Live /statusz block: slot table (who is in which batch row,
        how far along), queue depth, in-flight requests, occupancy and
        the resilience-ladder rung — the "what is the engine doing RIGHT
        NOW" view, distinct from the cumulative metrics()."""
        slots = []
        for i, e in enumerate(self.scheduler.slots.entries):
            if e is None:
                slots.append({"slot": i, "state": "free"})
                continue
            produced = int(sum(len(t) for t in e.tokens))
            slots.append({
                "slot": i, "state": "occupied",
                "request": e.request.id,
                "latency_class": e.request.latency_class,
                "prompt_len": int(len(e.request.prompt)),
                "max_new_tokens": e.request.max_new_tokens,
                "tokens_produced": produced,
                "chunks": e.chunks,
                "age_s": round(time.monotonic() - e.admitted_at, 4),
            })
        occupied = self.scheduler.slots.occupied()
        degraded = int(self._c_degr.value)
        return {
            "num_slots": self.num_slots,
            "chunk_size": self.chunk_size,
            "quant": self._b.quant,
            "replica_tag": self.replica_tag,
            "mesh": self._mesh_status(),
            "slots": slots,
            "occupancy_now": len(occupied) / self.num_slots,
            "queue_depth": len(self.scheduler),
            "in_flight": [s.request.id for _, s in occupied],
            "requests_submitted": self._next_id,
            "requests_completed": len(self._results),
            # the ladder rung the engine is effectively on: any chunk
            # degradation this lifetime means the per-token rung has
            # been exercised (per-request rungs ride each result record)
            "resilience": {
                "ladder_rung": "per_token" if degraded else "chunked",
                "degradations": degraded,
                "step_dispatches": self.step_dispatches,
            },
            "slo_targets": self.slo_targets,
            # deadline machinery: every shed class + the expired-row
            # partial returns — the "is admission control biting" view
            "shed": {
                "deadline": int(self._c_shed_deadline.value),
                "backpressure": int(self._c_shed_backpressure.value),
                "queue_deadline": int(self._c_shed_queue.value),
                "expired_rows": int(self._c_deadline_rows.value),
            },
            # crash-recovery evidence: when the last resumable snapshot
            # was written and where (None = never) — a monitoring rule
            # alerts on age, not existence
            "snapshot": (None if self._last_snapshot is None else {
                "path": self._last_snapshot[1],
                "age_s": round(time.monotonic()
                               - self._last_snapshot[0], 4),
                "count": int(self._c_snapshots.value),
                "every_chunks": self._snap_every or None,
            }),
            # what the prefix-cache pool holds RIGHT NOW (None =
            # disabled): occupancy, eviction counts and the bounded
            # slab table — also what a flight-recorder postmortem shows
            "prefix_cache": (None if self.prefix_cache is None
                             else self.prefix_cache.snapshot()),
            # speculative rung (None = engine not speculative):
            # ``active`` flips False after a speculative->chunked
            # demotion, the cumulative counters keep their totals
            "speculative": (None if not self._spec_configured else {
                "active": bool(self._spec_active),
                "num_speculative_tokens": int(self._b.K),
                "k_now": int(self._k_now),
                "adaptive_k": bool(self.adaptive_k),
                "rounds": int(self._c_spec_rounds.value),
                "accepted_drafts": int(self._c_spec_accept.value),
                "acceptance_len_mean": float(
                    self._g_spec_accept_mean.value),
                "overflow_tokens": int(self._c_spec_overflow.value),
                "draft_prefill_dispatches": int(
                    self._c_draft_prefill.value),
            }),
            # multi-tenant LoRA serving (None = no AdapterStore): the
            # store's registry + what the device stacks currently serve
            "adapters": (None if self.adapter_store is None else {
                **self.adapter_store.describe(),
                "served_version": int(self._b.lora_version),
                "swap_pending": bool(self.adapter_store.version
                                     != self._b.lora_version),
                "rows_by_adapter": {
                    name: int(c.value)
                    for name, c in sorted(
                        self._c_adapter_rows.items())},
            }),
            # device admission ring (None = host-scatter admission):
            # staged_now > 0 means prefill results are parked on device
            # waiting for the next chunk's fused splice
            "admission_ring": (None if not self._ring_slots else {
                "slots": int(self._ring_slots),
                "staged_now": sum(1 for m in self._ring_meta
                                  if m is not None),
                "staged": int(self._c_ring_staged.value),
                "scattered": int(self._c_ring_scattered.value),
                "full": int(self._c_ring_full.value),
                "host_scattered": int(self._c_host_scattered.value),
            }),
        }

    def _mesh_status(self) -> Optional[Dict[str, Any]]:
        """/statusz mesh block: the topology the engine serves on plus
        the LIVE carry's per-axis placements (read off the actual device
        arrays — evidence the state is sharded right now, not a config
        echo) and the dp slot grouping. ``None`` off-mesh."""
        srd = self._b.sharding
        if srd is None:
            return None
        from paddle_tpu.inference.sharding import DecodeSharding
        st = self.state
        kc0 = st.kc[0] if isinstance(st.kc, tuple) else st.kc
        d = srd.describe()
        d.pop("partition_rules", None)      # statusz stays small; rules
        #                                     live in bundle.json/README
        d["carry_sharding"] = {
            "logits": DecodeSharding.spec_str(st.logits),
            "kv_cache": DecodeSharding.spec_str(kc0),
            "pos": DecodeSharding.spec_str(st.pos),
            "keys": DecodeSharding.spec_str(st.keys),
        }
        d["dp_slot_groups"] = self.scheduler.dp_groups()
        return d

    def start_exporter(self, port: Optional[int] = None) -> int:
        """Start the live telemetry plane (obs/exporter.py) over this
        engine: /metrics scrapes the global obs registry + this engine's
        registry, /statusz carries :meth:`status`, /tracez the recent
        spans. ``port=None`` reads ``FLAGS_obs_export_port`` /
        ``PADDLE_TPU_OBS_PORT`` (0 there = don't start, returns 0).
        Returns the bound port. Idempotent while running."""
        if self._exporter is not None:
            return self._exporter.port
        from paddle_tpu.obs.exporter import ObsExporter, \
            resolve_export_port
        p = resolve_export_port() if port is None else int(port)
        if port is None and p == 0:
            return 0
        self._exporter = ObsExporter(port=p).add_engine(self)
        return self._exporter.start()

    def stop_exporter(self) -> None:
        """Stop the exporter and release its port (no-op when not
        running)."""
        exp, self._exporter = self._exporter, None
        if exp is not None:
            exp.stop()

    def metrics(self) -> Dict[str, Any]:
        """Serving metrics snapshot, derived from the engine's typed
        registry (``self.registry`` — counters/histograms a Prometheus
        endpoint could scrape via ``registry.to_prometheus()``).

        Every pre-obs key is preserved verbatim (dispatch accounting —
        prefills = admitted requests, chunks, per-token degradation
        steps; mean slot occupancy over chunk dispatches; queue-delay
        stats; the slot-steps useful-token denominator). New on top:
        p50/p99/mean REQUEST latency (submit -> finished, monotonic
        end-to-end) and queue-depth now/mean/peak snapshots.

        ``ttft_*`` runs from ADMISSION (it leaves the queue wait out);
        ``ttft_from_submit_*`` is what the caller saw. ``step_phase_s``
        holds the seconds and the number of intervals of each phase of
        ``step()``: ``admit``, ``dispatch``, ``wait`` and ``harvest``
        tile it (``admit``'s count is the steps so far), and
        ``admit_wait`` is the part of ``admit`` spent blocked on the
        row-key readback; ``max`` is the phase's longest interval.
        ``wait`` and ``admit_wait`` are device time;
        the host's own is admit - admit_wait + dispatch + harvest.
        ``device_timeline_s`` is the device's timeline as the host knows
        it (:class:`paddle_tpu.obs.DeviceTimeline`), the seconds of each
        part up to this call: ``fed.chunk`` / ``fed.prefill`` from the
        return of an enqueue to the end of the blocking read that waits
        it out, ``starved.admit`` / ``.dispatch`` / ``.harvest`` the
        device known empty with the host in that phase of ``step()``,
        ``starved.outside`` empty between two ``step()`` calls with work
        unfinished, ``no_work`` with none. The parts tile wall time since
        the engine was built, so the difference of their sum between two
        calls is the seconds between them. ``device_timeline_n`` counts
        the closed intervals of each part, and ``device_timeline_long``
        holds the newest 32 that lasted a second or more (``serial``,
        ``part``, ``seconds``; each also logged at WARNING on
        ``paddle_tpu.serving``) — ``no_work`` left out, an idle server
        is not stalled.
        ``live_kv_positions_total`` is the cache
        positions live at the start of every chunk dispatched so far (token
        positions, whatever ``cache_layers`` each holds; a position's bytes
        over all of them are ``cache_bytes_per_position``: the carry's
        bytes over slots x max_len, an average where windowed layers keep
        shorter buffers — ``cache_bytes_per_position_full`` /
        ``_window`` are the two kinds' own, and
        ``live_window_positions_total`` the positions live in the rolling
        buffers; for an EVA model (``cache_leaf_kinds`` 2) the
        latter counts the window leaf's live rows,
        ``live_summary_positions_total`` the visible summaries,
        ``cache_bytes_per_position_window`` / ``_summary`` a row's bytes of
        each leaf, and ``eva_*`` the chunks summarised, the window ends
        crossed, the windows prefilled and, by admission bucket, the rows,
        positions and attended pairs the prefills needed),
        ``live_latent_positions_total`` the positions a latent model's
        decode steps read over its latent layers, ``prefill_by_bucket``
        the admission prefills' rows, positions and causal (query, key)
        pairs by bucket (any model), ``moe_*`` what
        the routing of a model with routed feed-forwards did
        (``serving.moe.*``; zeros for any other) and
        ``moe_ffn_kernel_layers`` how many of its routed layers the chunk
        program runs through the grouped-FFN kernel,
        ``compiles`` this process's backend compiles by dispatch site."""
        qd, lat = self._h_qdelay, self._h_latency
        self._timeline.flush()
        return {
            "num_slots": self.num_slots,
            "chunk_size": self.chunk_size,
            "requests_submitted": self._next_id,
            "requests_completed": len(self._results),
            "queued": len(self.scheduler),
            "prefill_dispatches": self.prefill_dispatches,
            "chunk_dispatches": self.chunk_dispatches,
            "step_dispatches": self.step_dispatches,
            "degradations": int(self._c_degr.value),
            "occupancy_mean": self._h_occ.mean,
            "occupancy_samples": self._h_occ.count,
            # ALL rows compute every chunk step, occupied or not — the
            # honest denominator for useful-token occupancy comparisons
            "slot_steps_total": int(self._c_slot_steps.value),
            "step_phase_s": {ph: {"sum": h.sum, "count": h.count,
                                  "max": h.max}
                             for ph, h in self._h_phase.items()},
            "device_timeline_s": {part: c.value for part, c
                                  in self._c_timeline.items()},
            "device_timeline_n": {part: self._timeline.intervals[part]
                                  for part in self._c_timeline},
            "device_timeline_long": list(self._timeline.long),
            "live_kv_positions_total": int(self._c_live_kv.value),
            "live_window_positions_total": int(self._c_live_win.value),
            "cache_layers": self._cache_layers,
            "cache_bytes_per_position": self._cache_bytes_per_position,
            "cache_bytes_per_position_full": self._cache_bytes_full,
            "cache_bytes_per_position_window": self._cache_bytes_window,
            "cache_bytes_per_position_summary": self._cache_bytes_summary,
            "cache_leaf_kinds": self._leaf_kinds,
            "live_summary_positions_total": int(self._c_live_sum.value),
            "eva_chunks_summarised_total": int(self._c_eva_chunks.value),
            "eva_window_ends_total": int(self._c_eva_ends.value),
            "eva_prefill_windows_total": int(self._c_eva_prefill.value),
            "eva_prefill_by_bucket": {b: dict(v) for b, v
                                      in self._eva_prefill.items()},
            "live_latent_positions_total": int(self._c_live_latent.value),
            "prefill_by_bucket": {b: dict(v) for b, v
                                  in self._prefill_need.items()},
            "moe_pairs_held_total": int(self._c_moe_pairs.value),
            "moe_experts_touched_total": int(self._c_moe_touched.value),
            "moe_load_max": int(self._g_moe_load.value),
            "moe_ffn_kernel_layers": self._moe_ffn_kernel_layers,
            "compiles": obs.compile_counts(),
            "queue_delay_mean_s": qd.mean,
            "queue_delay_p50_s": qd.percentile(50),
            "queue_delay_p99_s": qd.percentile(99),
            "request_latency_mean_s": lat.mean,
            "request_latency_p50_s": lat.percentile(50),
            "request_latency_p99_s": lat.percentile(99),
            "queue_depth_now": int(self._g_qdepth.value),
            "queue_depth_peak": int(self._g_qdepth.max),
            "queue_depth_mean": self._h_qdepth.mean,
            # SLO instruments (NaN until the first sample — empty
            # reservoirs answer NaN, never a fake-fast 0.0)
            "ttft_mean_s": self._h_ttft.mean,
            "ttft_p50_s": self._h_ttft.percentile(50),
            "ttft_p99_s": self._h_ttft.percentile(99),
            "ttft_from_submit_p50_s": self._h_ttft_submit.percentile(50),
            "ttft_from_submit_p99_s": self._h_ttft_submit.percentile(99),
            "tpot_mean_s": self._h_tpot.mean,
            "tpot_p50_s": self._h_tpot.percentile(50),
            "slo_violations": int(sum(
                self.registry.get(n).value
                for n in self.registry.names()
                if ".slo." in n and n.endswith("_violations"))),
            # deadline machinery + crash-recovery cadence
            "shed_deadline": int(self._c_shed_deadline.value),
            "shed_backpressure": int(self._c_shed_backpressure.value),
            "shed_queue_deadline": int(self._c_shed_queue.value),
            "deadline_expired_rows": int(self._c_deadline_rows.value),
            "corrupt_rows": int(self._c_corrupt_rows.value),
            "rows_migrated_out": int(self._c_migrated_out.value),
            "rows_migrated_in": int(self._c_migrated_in.value),
            "snapshots": int(self._c_snapshots.value),
            "snapshot_age_s": (
                None if self._last_snapshot is None
                else round(time.monotonic() - self._last_snapshot[0], 4)),
            # admission economics: dispatches avoided (full hits +
            # batched groups), tokens of prefill compute skipped, and
            # per-hit-class admission latency (NaN until a class has a
            # sample)
            "admission_dispatches_saved": int(self._c_disp_saved.value),
            "admission_cache_reordered": int(self._c_reordered.value),
            "batched_admission_groups": int(
                self._c_batched_groups.value),
            "prefill_tokens_saved": int(self._c_tokens_saved.value),
            "admission_p50_s": {cls: h.percentile(50)
                                for cls, h in self._h_admit.items()},
            "admission_p99_s": {cls: h.percentile(99)
                                for cls, h in self._h_admit.items()},
            "prefix_cache": (None if self.prefix_cache is None else {
                **self.prefix_cache.stats(),
                "engine_hits_full": int(self._c_prefix["full"].value),
                "engine_hits_partial": int(
                    self._c_prefix["partial"].value),
                "engine_misses": int(self._c_prefix["miss"].value),
            }),
            # dispatch accounting for the speculative rung: draft ring
            # prefills are real dispatches, counted separately so
            # tokens-per-dispatch stays honest
            "draft_prefill_dispatches": int(self._c_draft_prefill.value),
            "speculative": (None if not self._spec_configured else {
                "active": bool(self._spec_active),
                "num_speculative_tokens": int(self._b.K),
                "k_now": int(self._k_now),
                "adaptive_k": bool(self.adaptive_k),
                "rounds": int(self._c_spec_rounds.value),
                "accepted_drafts": int(self._c_spec_accept.value),
                "acceptance_len_mean": float(
                    self._g_spec_accept_mean.value),
                "overflow_tokens": int(self._c_spec_overflow.value),
            }),
            "admission_ring": (None if not self._ring_slots else {
                "slots": int(self._ring_slots),
                "staged": int(self._c_ring_staged.value),
                "scattered": int(self._c_ring_scattered.value),
                "full": int(self._c_ring_full.value),
                "host_scattered": int(self._c_host_scattered.value),
            }),
            # multi-tenant LoRA serving (None = no AdapterStore): the
            # per-adapter row counts are the /metrics proof a mixed
            # batch shared the fused dispatch
            "adapters": (None if self.adapter_store is None else {
                "active": int(self._g_adapters_active.value),
                "swaps": int(self._c_adapter_swaps.value),
                "store_version": int(self.adapter_store.version),
                "rows_by_adapter": {
                    name: int(c.value)
                    for name, c in sorted(
                        self._c_adapter_rows.items())},
            }),
            "stream_ttft_p50_s": {
                cls: h.percentile(50)
                for cls, h in sorted(self._h_stream_ttft.items())},
        }
