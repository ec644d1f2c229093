"""Mesh sharding for the decode/serving stack (GSPMD tensor parallelism).

Pope et al. (2211.05102, PAPERS.md): small-batch decode is
weight-bandwidth-bound per chip, so splitting attention heads and the
MLP hidden dim over a ``tp`` mesh axis is the direct tokens/s-per-replica
lever, and the batch (= the serving engine's slot table) rides a ``dp``
axis for data-parallel replicas. GSPMD (Xu et al.) is the mechanism: we
annotate placements, XLA inserts the collectives.

``DecodeSharding`` is the one object the whole stack shares:

- regex partition rules (``DEFAULT_DECODE_RULES``, the SNIPPETS.md
  ``match_partition_rules`` idiom) shard the decoder's fused param dict
  — qkv/gate_up column-parallel, o_proj/down_proj row-parallel,
  vocab-parallel embedding and lm head;
- the ``DecodeState`` carry lives sharded ON DEVICE across chunks: KV
  caches on ``(dp, tp-on-heads)``, per-row positions/keys/done/eos/temp
  on ``dp`` — re-entry and engine admission never gather to host;
- every placement passes the divisibility guard
  (``parallel.placements.guarded_spec``): an axis that cannot split a
  dim evenly replicates that dim instead. Replication is always
  numerically correct under GSPMD, so any model/mesh combination runs —
  the guard only costs efficiency, never parity.

Parity contract (enforced by tests on the 8-virtual-device CPU harness):
sharded decode emits bit-identical TOKENS to the single-device path for
greedy and per-row-keyed sampling. Logits may differ in float ulps
(sharded matmuls reassociate reductions); argmax/categorical picks are
insensitive to that except on exact ties, which measure-zero never hits.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

__all__ = ["DecodeSharding", "DEFAULT_DECODE_RULES", "MeshMismatchError",
           "SpeculativeMeshError", "QuantizedKVMeshError"]


class MeshMismatchError(ValueError):
    """A mesh/sharding contract violation: a bundle exported for one mesh
    loaded under another, an engine mesh that contradicts its backend's,
    or too few devices for a recorded topology."""


class QuantizedKVMeshError(NotImplementedError):
    """The ``int8wk`` recipe (int8 KV cache + per-row scales) is not
    supported on a mesh yet: the quantized carry's scale buffers have no
    partition rules and the hand-written kernels gate off under GSPMD
    anyway, so the bandwidth win would not materialize. ``int8w``
    (weight-only) DOES serve on a mesh — the dequant matmul falls back
    to the XLA form, which shards like any dot. Typed so decoder
    construction refuses up front, never a mid-dispatch failure."""


class SpeculativeMeshError(NotImplementedError):
    """Historically: speculative decoding refused on a mesh. The live
    decode path now RUNS speculation under dp/tp meshes — the per-row
    uneven cache advance lowers through ``shard_map`` (dp splits the
    batch, tp splits heads; the per-row dynamic-update-slice needs no
    collectives, so the local-shard body is the single-device body) and
    is parity-tested bit-exact on the virtual CPU mesh. The type remains
    for the one surface that still refuses: exporting a SPECULATIVE AOT
    bundle from a mesh-built decoder (``export_decoder_bundle``), where
    the serialized entries would bake the mesh topology into the draft
    programs. Typed so the refusal stays up-front and the resilience
    classifier treats it as fatal, never a retry/degrade candidate."""


# Megatron-parity rules over the DECODE param dict (_build_params names:
# fused qkv / gate_up, optional :int8/:scale splits, precomputed rope).
# Column-parallel weights shard dim 1, row-parallel dim 0; the int8
# per-output-channel scale follows its matrix's output dim. Vocab axes
# (embedding rows, head columns) shard on tp — logits come out
# vocab-sharded and argmax/sampling reduce across the axis in-program
# (XLA inserts the gather; "sharded sampling" rather than a host trip).
DEFAULT_DECODE_RULES: Tuple[Tuple[str, tuple], ...] = (
    # stacked LoRA delta pairs (serving/lora): FIRST — their names embed
    # the host matrix names, and first-match would otherwise hand a 3-D
    # stack a 2-D host rule. Replicated: rank-r stacks are tiny next to
    # their host matrices and replication keeps the per-row gather
    # collective-free on any mesh (sharding B's d_out on tp like the
    # host column-parallel matrices is a valid refinement — measure
    # before switching).
    (r"^lora\.", ()),
    (r"self_attn\.qkv\.weight:scale", ("tp",)),
    (r"mlp\.gate_up\.weight:scale", ("tp",)),
    (r"(o_proj|down_proj)\.weight:scale", ()),
    (r"^head:scale", ("tp",)),
    (r"self_attn\.qkv\.weight", (None, "tp")),
    (r"self_attn\.o_proj\.weight", ("tp", None)),
    (r"mlp\.gate_up\.weight", (None, "tp")),
    (r"mlp\.down_proj\.weight", ("tp", None)),
    (r"embed_tokens\.weight", ("tp", None)),
    (r"lm_head\.weight", (None, "tp")),
    (r"^head", (None, "tp")),
    (r"rope\.(cos|sin)", ()),
    (r".*", ()),                      # norms and anything else: replicate
)


class DecodeSharding:
    """The decode stack's mesh + partition plan.

    ``mesh``: a ``ProcessMesh`` / ``jax.sharding.Mesh`` / ``"dp:2,tp:4"``
    spec (``parallel.mesh.decode_mesh`` accepts all three). ``dp`` and
    ``tp`` are conventional axis names — axes the rules don't mention
    replicate, so e.g. a pure-``tp`` mesh serves a single replica.
    """

    def __init__(self, mesh, rules: Optional[Sequence] = None,
                 dp_axis: str = "dp", tp_axis: str = "tp"):
        from paddle_tpu.parallel.mesh import decode_mesh
        self.mesh = decode_mesh(mesh)
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.rules = tuple((str(r), tuple(e)) for r, e in
                           (rules if rules is not None
                            else DEFAULT_DECODE_RULES))

    # -- mesh surface -------------------------------------------------------
    @property
    def jax_mesh(self):
        return self.mesh.jax_mesh

    @property
    def size(self) -> int:
        return self.mesh.size

    @property
    def axes(self) -> Dict[str, int]:
        return {n: self.mesh.dim_size(n) for n in self.mesh.dim_names}

    def dp_size(self) -> int:
        return (self.mesh.dim_size(self.dp_axis)
                if self.dp_axis in self.mesh.dim_names else 1)

    def dp_shards(self, batch: int) -> int:
        """How many ways the guard actually splits a ``batch``-row carry
        on dp (1 when the batch doesn't divide — the slot table then maps
        onto a single replica)."""
        d = self.dp_size()
        return d if d > 1 and batch % d == 0 else 1

    def same_topology(self, other: "DecodeSharding") -> bool:
        return self.axes == other.axes

    # -- spec construction --------------------------------------------------
    def named(self, shape, entries):
        """Guarded ``NamedSharding`` for one array shape."""
        from jax.sharding import NamedSharding

        from paddle_tpu.parallel.placements import guarded_spec
        return NamedSharding(self.jax_mesh,
                             guarded_spec(shape, entries, self.mesh))

    def guarded(self, shape, entries):
        """Guarded raw ``PartitionSpec`` for one array shape — what
        ``shard_map`` in/out_specs take (``named`` wraps the same spec in
        a NamedSharding for device_put/constraint use)."""
        from paddle_tpu.parallel.placements import guarded_spec
        return guarded_spec(shape, entries, self.mesh)

    def state_entries(self, field: str, ndim: int,
                      head_major: Optional[bool] = None) -> tuple:
        """Spec entries for one ``DecodeState`` field."""
        dp, tp = self.dp_axis, self.tp_axis
        if field == "logits":              # (B, V): vocab-sharded logits
            return (dp, tp)
        if field in ("pos", "done", "eos", "temp", "tok", "spec_rounds",
                     "spec_accepted", "nv", "adapter_idx", "spec_on"):
            return (dp,)
        if field == "keys":                # (B, 2) raw uint32 keys
            return (dp, None)
        if field in ("kc", "vc", "dkc", "dvc"):
            off = ndim - 4
            e = [None] * ndim
            e[off] = dp
            if head_major is not None:
                e[off + (1 if head_major else 2)] = tp
            return tuple(e)
        raise ValueError(f"unknown DecodeState field {field!r}")

    # -- params -------------------------------------------------------------
    def param_specs(self, params: Dict[str, object]) -> Dict[str, tuple]:
        from paddle_tpu.parallel.placements import match_partition_rules
        return match_partition_rules(self.rules, params)

    def shard_params(self, params: Dict[str, object]) -> Dict[str, object]:
        from paddle_tpu.parallel.placements import shard_by_rules
        return shard_by_rules(params, self.mesh, self.rules)

    # -- arrays / carries ---------------------------------------------------
    def put(self, x, entries):
        """Commit one array to its guarded sharding (host -> mesh)."""
        import jax
        return jax.device_put(x, self.named(np.shape(x), entries))

    def put_state_field(self, field: str, x, head_major: bool):
        import jax
        if x is None:
            return None
        if isinstance(x, tuple):          # per-layer cache buffers
            return tuple(self.put_state_field(field, b, head_major)
                         for b in x)
        ns = self.named(np.shape(x),
                        self.state_entries(field, np.ndim(x), head_major))
        return jax.device_put(x, ns)

    def put_state(self, state, head_major: bool):
        """Commit a whole ``DecodeState`` to its on-mesh placements."""
        import dataclasses
        kw = {}
        for f in ("logits", "kc", "vc", "pos", "keys", "done", "eos",
                  "temp", "dkc", "dvc", "tok", "spec_rounds",
                  "spec_accepted", "nv", "adapter_idx", "spec_on"):
            v = getattr(state, f, None)
            if v is None:
                continue                  # plain carries skip spec fields
            kw[f] = self.put_state_field(f, v, head_major)
        return dataclasses.replace(state, **kw)

    def constrain(self, x, field: str, head_major: bool):
        """``with_sharding_constraint`` inside a traced function — the
        sharding-preserving-jit half of the contract: carry OUTPUTS are
        pinned to the same placements the inputs arrived with, so chunk
        re-entry is a fixed-signature cache hit and the carry can never
        silently decay to replicated/host between dispatches."""
        import jax
        if x is None:
            return None
        if isinstance(x, tuple):
            return tuple(self.constrain(b, field, head_major) for b in x)
        ns = self.named(tuple(x.shape),
                        self.state_entries(field, x.ndim, head_major))
        return jax.lax.with_sharding_constraint(x, ns)

    def constrain_carry(self, head_major: bool, **fields) -> tuple:
        """``constrain`` over the named ``DecodeState`` fields a program
        returns (``logits=``, ``kc=``, ``eos=``, ``adapter_idx=`` ...; a
        ``None`` value stays ``None``), in the order given: the one call
        every carry-returning program and the admission scatter pin
        their outputs through."""
        return tuple(self.constrain(v, f, head_major)
                     for f, v in fields.items())

    # -- metadata (bundle.json / statusz / bench records) -------------------
    def describe(self) -> Dict[str, object]:
        """The recordable topology: ordered axes, device kind, the rule
        list — what ``export_decoder_bundle`` writes into
        ``decode_mode.mesh`` and ``ServingEngine.status()`` reports."""
        import jax
        try:
            kind = str(self.jax_mesh.devices.reshape(-1)[0].device_kind)
        except Exception:
            kind = str(jax.devices()[0].device_kind)
        return {
            "axes": dict(self.axes),
            "size": self.size,
            "dp_axis": self.dp_axis,
            "tp_axis": self.tp_axis,
            "device_kind": kind,
            "partition_rules": [[r, list(e)] for r, e in self.rules],
        }

    @classmethod
    def from_describe(cls, meta: Dict[str, object]) -> "DecodeSharding":
        """Rebuild the sharding from a recorded description (bundle
        load). Raises :class:`MeshMismatchError` when this process does
        not have enough devices for the recorded topology."""
        import jax
        axes = dict(meta["axes"])
        size = int(np.prod([int(v) for v in axes.values()]))
        if jax.device_count() < size:
            raise MeshMismatchError(
                f"recorded mesh {axes} needs {size} devices; this "
                f"process has {jax.device_count()}")
        rules = [(r, tuple(e)) for r, e in meta.get("partition_rules",
                                                    DEFAULT_DECODE_RULES)]
        return cls(axes, rules=rules,
                   dp_axis=meta.get("dp_axis", "dp"),
                   tp_axis=meta.get("tp_axis", "tp"))

    @staticmethod
    def spec_str(x) -> str:
        """Human/JSON form of a live array's sharding spec (statusz)."""
        try:
            return str(getattr(x.sharding, "spec", x.sharding))
        except Exception:
            return "unknown"

    def __repr__(self):
        return (f"DecodeSharding(axes={self.axes}, "
                f"devices={self.size})")
