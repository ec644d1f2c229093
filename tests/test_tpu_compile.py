"""AOT compiles of the main path's Pallas kernels for a described TPU.

The TPU's compiler is installed with jax and compiles for a topology that
is described, not attached (`v5e:2x2`), so these run on the CPU harness and
still raise what the chip's compiler would raise: a slice off the tiling,
more scoped VMEM than a kernel may use, a program that does not fit. The
shapes are Llama-2-7B's (hidden 4096, 32 heads of 128, FFN 11008).
Interpret mode is switched off by patching the one routing helper, not by
an option of the program. Nothing runs: a compile that passes says nothing
about results or times.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def tpu():
    """SingleDeviceSharding on one chip of a described v5e 2x2, with the
    persistent compile cache off around the module (an AOT entry written
    for a described chip cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def decoder_kernels_routed():
    """The decoder's kernel routing asks for a TPU backend or this flag."""
    import paddle_tpu as paddle
    paddle.set_flags({"decode_attention_interpret": True})
    yield
    paddle.set_flags({"decode_attention_interpret": False})


@pytest.fixture(autouse=True)
def compiled_not_interpreted(monkeypatch):
    from paddle_tpu.ops.pallas import _routing
    monkeypatch.setattr(_routing, "use_interpret", lambda: False)


def _kernels(fn, *args):
    from paddle_tpu.obs.cost import program_census
    return program_census(jax.jit(fn).lower(*args).compile())["kernels"]


def _s(tpu, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu)


def test_flash_attention_fwd_bwd_compiles(tpu):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fn

    def loss(q, k, v):
        return flash_attention_fn(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = [_s(tpu, (2, 2048, 32, 128))] * 3
    kernels = _kernels(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert kernels == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rms_norm_fwd_bwd_compiles_at_hidden_4096(tpu, dtype):
    """The case a fixed 256-row block failed: at h=4096 the backward's
    blocks and temporaries passed the 16 MiB scoped-VMEM limit."""
    from paddle_tpu.ops.pallas import rms_norm

    rows, h = 4096, 4096
    assert rms_norm.supported((2, 2048, h), (h,))
    x, w = _s(tpu, (rows, h), dtype), _s(tpu, (h,), jnp.float32)
    assert _kernels(lambda x, w: rms_norm.rms_fwd(x, w, 1e-6), x, w) \
        == {"rms_norm_fwd": 1}
    assert _kernels(rms_norm.rms_bwd, x, w, _s(tpu, (rows, 1), jnp.float32),
                    _s(tpu, (rows, h), dtype)) == {"rms_norm_bwd": 1}


@pytest.mark.parametrize("shape,kv_dtype", [
    ((8, 32, 8, 4096, 128), jnp.bfloat16),
    ((8, 32, 8, 4096, 128), jnp.int8),
    # the Mistral serving cell's own: 16 slots, max_len 2048
    ((16, 32, 8, 2048, 128), jnp.bfloat16),
])
def test_decode_attention_compiles(tpu, shape, kv_dtype):
    """All KV heads of a row ride one grid step, so the block of cache
    positions the kernel picks (``_block_len``) has to keep its tiles —
    and an int8 cache's lane-padded scale tiles — inside scoped VMEM."""
    from paddle_tpu.ops.pallas.decode_attention import (
        _block_len, decode_attention)

    B, H, KV, L, D = shape
    quant = kv_dtype == jnp.int8
    assert _block_len(256, L, KV, D, jnp.dtype(kv_dtype).itemsize,
                      quant) == 256
    q, pos = _s(tpu, (B, H, D)), _s(tpu, (B,), jnp.int32)
    kc = _s(tpu, (B, KV, L, D), kv_dtype)
    if quant:
        sc = _s(tpu, (B, KV, L, 1), jnp.float32)
        kernels = _kernels(
            lambda q, k, v, p, ks, vs: decode_attention(
                q, k, v, p, k_scale=ks, v_scale=vs), q, kc, kc, pos, sc, sc)
    else:
        kernels = _kernels(decode_attention, q, kc, kc, pos)
    assert kernels == {"decode_attention": 1}


def test_two_leaf_decode_attention_compiles_at_the_evabyte_cells_shape(tpu):
    """``decode_attention_pair`` at evabyte.serve.longdoc-backlog's own
    leaves: 8 slots, 32 MHA heads (one query head a KV head), a window
    leaf of 2048 rows beside 32768 / 16 summaries; four tiles in flight,
    so the block of positions halves to 128 to stay inside scoped VMEM."""
    from paddle_tpu.ops.pallas.decode_attention import (
        _pair_block_len, decode_attention_pair)

    B, H, L, D = 8, 32, 2048, 128
    assert _pair_block_len(256, L, L, H, D, 2) == 128
    q, n = _s(tpu, (B, H, D)), _s(tpu, (B,), jnp.int32)
    leaf = _s(tpu, (B, H, L, D))
    assert _kernels(decode_attention_pair, q, leaf, leaf, n, leaf, leaf,
                    n) == {"decode_attention_pair": 1}


def test_eva_chunk_pool_compiles_at_the_evabyte_cells_shape(tpu):
    """A decode step's chunk summary from the window leaf: 8 slots, 32
    heads, chunks of 16 rows (one bf16 sublane tile) of 128."""
    from paddle_tpu.ops.pallas.eva_attention import eva_chunk_pool

    leaf, vec = _s(tpu, (8, 32, 2048, 128)), _s(tpu, (32, 128))
    assert _kernels(lambda k, v, mu, phi, at: eva_chunk_pool(
        k, v, mu, phi, at, chunk=16), leaf, leaf, vec, vec,
        _s(tpu, (8,), jnp.int32)) == {"eva_chunk_pool": 1}


@pytest.mark.parametrize("positions", [2048, 6144, 32768])
def test_eva_prefill_attention_compiles_at_the_cells_buckets(tpu, positions):
    """The EVA admission prefill's one attention kernel: 32 heads of 128,
    windows of 2048 and 128 summaries a window — one window (the check's
    2044 positions), three (its 4100: a summary block of 384) and the
    longest bucket."""
    from paddle_tpu.ops.pallas.eva_attention import eva_prefill_attention

    x = _s(tpu, (32, positions, 128))
    s_ = _s(tpu, (32, positions // 16, 128))
    assert _kernels(lambda q, k, v, ks, vs: eva_prefill_attention(
        q, k, v, ks, vs, window=2048, per=128), x, x, x, s_, s_) \
        == {"eva_prefill_attention": 1}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape,head_major", [
    ((16, 8, 2048, 128), True),     # mistral7b.serve.*
    ((96, 8, 1024, 128), True),     # trinity.serve.reason-backlog
    ((8, 2048, 32, 128), False),    # deepseek7b.serve.backlog
    ((8, 1024, 16, 128), False),    # ouro2.6b.serve.reason-backlog
])
def test_kv_row_write_compiles_at_the_cells_cache_shapes(tpu, shape,
                                                         head_major, dtype):
    """One call writes a layer's K row and V row for every sequence, in
    place: both caches come back aliased (given up by the caller, they
    are the outputs), and nothing of a cache's size is held beside them."""
    from paddle_tpu.obs.cost import program_census
    from paddle_tpu.ops.pallas.kv_row_write import kv_row_write, supported

    B = shape[0]
    rows = ((B, shape[1], 1, 128) if head_major
            else (B, 1, shape[2], 128))
    kc, k = _s(tpu, shape, dtype), _s(tpu, rows, dtype)
    assert supported(kc, k, head_major)
    compiled = jax.jit(
        lambda kc, vc, k, v, at: kv_row_write(kc, vc, k, v, at,
                                              head_major=head_major),
        donate_argnums=(0, 1)).lower(
            kc, kc, k, k, _s(tpu, (B,), jnp.int32)).compile()
    assert program_census(compiled)["kernels"] == {"kv_row_write": 1}
    mem = compiled.memory_analysis()
    cache = 2 * jnp.dtype(dtype).itemsize * B * shape[1] * shape[2] * 128
    assert mem.alias_size_in_bytes == cache
    assert mem.temp_size_in_bytes < 1 << 20
    assert _whole_cache_writers(
        compiled.as_text(), {",".join(map(str, shape))},
        entry=True) == []


def test_int8_matmul_compiles(tpu):
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul

    kernels = _kernels(int8_matmul, _s(tpu, (8, 4096)),
                       _s(tpu, (4096, 11008), jnp.int8),
                       _s(tpu, (11008,), jnp.float32))
    assert kernels == {"int8_matmul": 1}


def test_kernels_give_way_where_gspmd_would_split_them(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: under a mesh of
    several devices the routing predicates decline (the XLA forms shard),
    inside a fully manual shard_map they do not."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas import _routing, flash_attention, rms_norm
    from paddle_tpu.parallel import mesh as pmesh

    qkv = (2, 2048, 32, 128)
    assert not _routing.auto_partitioned()
    assert rms_norm.supported((4096, 4096), (4096,))
    assert flash_attention.supported(qkv, qkv, True)
    monkeypatch.setattr(pmesh, "_GLOBAL_MESH",
                        pmesh.ProcessMesh(shape=(2, 2),
                                          dim_names=("dp", "mp")))
    assert _routing.auto_partitioned()
    assert not rms_norm.supported((4096, 4096), (4096,))
    assert not flash_attention.supported(qkv, qkv, True)
    seen = []

    def body(x):
        seen.append(_routing.auto_partitioned())
        return x

    m = pmesh.get_mesh().jax_mesh
    jax.jit(jax.shard_map(body, mesh=m, in_specs=P("dp", "mp"),
                          out_specs=P("dp", "mp"), check_vma=False)
            ).lower(jax.ShapeDtypeStruct((4, 4), np.float32))
    assert seen == [False]


def _whole_cache_writers(hlo_text, shapes, entry=False):
    """Instructions outside the entry computation (inside it too with
    ``entry``) and outside fusions' bodies whose output has one of
    ``shapes`` (dims as ``"16,8,2048,128"``) and is materialised:
    everything but parameters, tuple plumbing, bitcasts and the in-place
    row update — a ``dynamic-update-slice``, bare or as the root of a
    fusion, whose update operand is smaller than the smallest of
    ``shapes``, one layer; or an output of a kernel's custom call that
    ``output_to_operand_aliasing`` says IS one of its operands (a kernel
    output of a cache's shape that is not aliased is a second cache)."""
    import re
    instr = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                       r"([\w\-]+)\((.*)")
    kernel = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = \((.*?)\) "
                        r"custom-call\((.*)")
    comps, fused, cur, found = {}, set(), None, []
    for line in hlo_text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(2), {})
            if head.group(1) and not entry:
                comps.pop(head.group(2))     # the entry's copies are not
                cur = {}                     # the loop's (carry not donated)
            continue
        m = kernel.match(line)
        if m and cur is not None:
            name, outs, rest = m.groups()
            aliased = re.search(r"output_to_operand_aliasing=\{(.*?)\}, \w+=",
                                rest)
            for i, (_, dims) in enumerate(
                    re.findall(r"(\w+)\[([\d,]*)\]", outs)):
                if dims in shapes and not (
                        aliased and f"{{{i}}}: (" in aliased.group(1)):
                    found.append(f"{name}: output {i} [{dims}] not aliased")
            continue
        m = instr.match(line)
        if m and cur is not None:
            name, _, dims, op, rest = m.groups()
            called = re.search(r"calls=%?([\w.\-]+)", rest)
            if op == "fusion" and called:
                fused.add(called.group(1))
            cur[name] = (dims, op, re.findall(r"%([\w.\-]+)", rest),
                         called.group(1) if called else None,
                         line.lstrip().startswith("ROOT"))

    def elems(dims):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        return n

    def row_update(comp, name):
        dims, op, operands, called, _ = comp[name]
        if op == "fusion":
            body = comps[called]
            root = next(n for n, v in body.items() if v[4])
            return row_update(body, root)
        return (op == "dynamic-update-slice"
                and elems(comp[operands[1]][0]) < min(map(elems, shapes)))

    for cname, comp in comps.items():
        if cname in fused:
            continue
        for name, (dims, op, _, _, _) in comp.items():
            if dims in shapes and op not in (
                    "parameter", "get-tuple-element", "bitcast") \
                    and not row_update(comp, name):
                found.append(f"{cname}: {name} = {op} [{dims}]")
    return found


def _loops_beside(hlo_text, kernel):
    """``while`` instructions in the computation that holds the
    ``kernel`` custom calls and in everything it calls; that computation
    has to be the body of the chunk program's step loop. (XLA's own
    lowering of the per-row write is a ``while`` over the batch, a
    buffer: 2 x layers of them a step.)"""
    import re
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    holds = [c for c, lines in comps.items()
             if any(f"%{kernel}." in ln and "tpu_custom_call" in ln
                    for ln in lines)]
    assert len(holds) == 1, holds
    loop = [ln for lines in comps.values() for ln in lines
            if f"body=%{holds[0]}," in ln or f"body=%{holds[0]} " in ln]
    assert len(loop) == 1 and \
        'op_name="jit(ring_chunk_decode)/while"' in loop[0], loop
    seen, todo, whiles = set(), [holds[0]], []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            if re.search(r"\bwhile\(", ln):
                whiles.append(f"{c}: {ln.strip()[:120]}")
            todo += [n for n in re.findall(r"%([\w.\-]+)", ln)
                     if n in comps]
    return whiles


@pytest.mark.parametrize("kv_heads,slots,layer_shape", [
    (8, 16, "16,8,2048,128"),     # GQA, head-major, both kernels
    (32, 8, "8,32,2048,128"),     # MHA, head-major (PR 39), both kernels
])
def test_ring_chunk_program_updates_the_kv_carry_in_place(
        tpu, decoder_kernels_routed, kv_heads, slots, layer_shape):
    """The serving chunk program at 7B attention widths (hidden 4096, 32
    heads of 128, ``max_len`` 2048; FFN and vocabulary small) holds no
    copy of a layer's KV buffer: its temporaries stay under one layer's K
    buffer, and inside the 16-step loop nothing outputs a whole layer but
    the in-place token-row update: one ``kv_row_write`` call a cache
    layer, both of its outputs aliased to its cache operands, and no loop
    over the batch beside it. (With the carry stacked over layers the
    same program held a slice of each layer out of the stack and a write
    of it back, every layer of every step: temporaries of 271 MB and
    543 MB.)"""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs.cost import program_census

    # the routing asks for a TPU backend or the flag (decoder_kernels_routed);
    # the kernels are then compiled, not interpreted
    # (compiled_not_interpreted)
    layers, vocab, max_len, steps = 2, 512, 2048, 16
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=vocab, hidden_size=4096, intermediate_size=256,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=kv_heads, max_position_embeddings=max_len,
        dtype="bfloat16"))
    model.to(dtype="bfloat16")
    dec = LlamaDecoder(model, max_len=max_len)

    def on_chip(tree):
        return jax.tree.map(lambda a: _s(tpu, a.shape, a.dtype), tree)

    kc, vc = on_chip(jax.eval_shape(lambda: dec._empty_cache(slots)))
    assert len(kc) == layers and kc[0].shape == tuple(
        int(d) for d in layer_shape.split(","))
    logits = _s(tpu, (slots, vocab), jnp.float32)
    rows_i32, rows_f32 = (_s(tpu, (slots,), dt)
                          for dt in (jnp.int32, jnp.float32))
    keys = _s(tpu, (slots, 2), jnp.uint32)
    done = _s(tpu, (slots,), jnp.bool_)
    compiled = dec._ring_chunk_decode._jitted.lower(
        on_chip(dec.params), logits, kc, vc, rows_i32, keys, done,
        rows_i32, rows_f32, None,
        # the admission ring: as many rows as the carry has slots
        logits, kc, vc, rows_i32, rows_i32, keys, rows_i32, rows_f32, None,
        steps=steps, do_sample=False, top_k=None, top_p=None).compile()

    kernels = program_census(compiled)["kernels"]
    assert kernels == {"decode_attention": layers, "kv_row_write": layers}
    # under one layer's K buffer (67 MB in the GQA case, 134 MB in the MHA)
    assert compiled.memory_analysis().temp_size_in_bytes < 67_108_864
    stack_shape = f"{layers},{layer_shape}"
    assert _whole_cache_writers(compiled.as_text(),
                                {layer_shape, stack_shape}) == []
    assert _loops_beside(compiled.as_text(), "kv_row_write") == []


def test_looped_chunk_program_keeps_every_pass_cache_in_place(
        tpu, decoder_kernels_routed):
    """A looped model's chunk program (models/ouro.py: 2 weight layers run
    3 times, so 6 cache layers; Ouro-2.6B's attention widths, small FFN and
    vocabulary) writes each pass's token rows into that pass's own buffer
    in place: the passes are unrolled at trace time, so no cache buffer is
    indexed by a traced pass number and none is copied whole inside the
    step loop (PERF.md section 6, PR 28); one ``kv_row_write`` call and,
    since the cache went head-major (PR 39), one ``decode_attention`` call
    a cache layer."""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
    from paddle_tpu.obs.cost import program_census

    layers, passes, vocab, max_len, slots, steps = 2, 3, 512, 1024, 8, 16
    model = OuroForCausalLM(OuroConfig(
        vocab_size=vocab, hidden_size=2048, intermediate_size=256,
        num_hidden_layers=layers, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=max_len,
        total_ut_steps=passes, dtype="bfloat16"))
    model.to(dtype="bfloat16")
    dec = LlamaDecoder(model, max_len=max_len)

    def on_chip(tree):
        return jax.tree.map(lambda a: _s(tpu, a.shape, a.dtype), tree)

    kc, vc = on_chip(jax.eval_shape(lambda: dec._empty_cache(slots)))
    assert len(kc) == layers * passes
    assert kc[0].shape == (slots, 16, max_len, 128)     # head-major, PR 39
    logits = _s(tpu, (slots, vocab), jnp.float32)
    rows_i32, rows_f32 = (_s(tpu, (slots,), dt)
                          for dt in (jnp.int32, jnp.float32))
    keys = _s(tpu, (slots, 2), jnp.uint32)
    done = _s(tpu, (slots,), jnp.bool_)
    compiled = dec._ring_chunk_decode._jitted.lower(
        on_chip(dec.params), logits, kc, vc, rows_i32, keys, done,
        rows_i32, rows_f32, None,
        logits, kc, vc, rows_i32, rows_i32, keys, rows_i32, rows_f32, None,
        steps=steps, do_sample=False, top_k=None, top_p=None).compile()
    assert program_census(compiled)["kernels"] == {
        "decode_attention": layers * passes, "kv_row_write": layers * passes}
    # under one cache buffer (33.5 MB): nothing holds a copy of one
    assert compiled.memory_analysis().temp_size_in_bytes < 33_554_432
    text = compiled.as_text()
    writers = _whole_cache_writers(text, {"8,16,1024,128"})
    # what is left may only be the compiler's memory-space assignment
    # keeping a buffer in on-chip memory (S(1)) across the two kernel calls
    # that read it — at this tiny FFN much of that memory is free; at
    # Ouro's own widths it moves one buffer of 48 where the token-major
    # parent moved six (AOT, PR 39: PERF.md section 4)
    assert [w for w in writers if not _on_chip_move(text, w)] == []
    assert _loops_beside(text, "kv_row_write") == []


def _on_chip_move(hlo_text, writer):
    """Whether ``writer`` (an entry of ``_whole_cache_writers``) is a
    buffer's move into on-chip memory (a ``ConcatBitcast`` of prefetched
    slices whose value lives in memory space 1) or back out of it (the
    ``copy-done`` of a ``copy-start`` from memory space 1)."""
    import re
    name = writer.split(": ")[1].split(" = ")[0]
    start = name.replace("copy-done", "copy-start")
    line = next(ln for ln in hlo_text.splitlines()
                if re.match(rf"\s*(?:ROOT )?%?{re.escape(start)} = ", ln))
    if name.startswith("copy-done"):
        return "S(1)}" in line.split(" copy-start(")[0]
    return ('custom_call_target="ConcatBitcast"' in line
            and "S(1)}" in line.split(" custom-call(")[0])


def test_banded_flash_forward_compiles_at_the_long_prefill_shape(
        tpu, decoder_kernels_routed):
    """The admission prefill of a windowed model at its longest bucket:
    8192 positions, 48 heads of 128, a band of 4096 (clamped k/v index
    maps, a second skip condition); and the routed feed-forward over one
    chip's 32 experts at the two serving sections' 16 and 96 rows: one
    grouped-FFN kernel call (ops/pallas/grouped_ffn.py, its scoped VMEM
    asked for as stated there) and temporaries under 16 MiB; off the
    kernels' backend XLA's two ``ragged-dot`` calls and no kernel, their
    temporaries under 16 MiB too."""
    import paddle_tpu as paddle
    from paddle_tpu.obs.cost import program_census
    from paddle_tpu.ops.moe import routed_ffn
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fn

    qkv = [_s(tpu, (1, 8192, 48, 128))] * 3
    kernels = _kernels(lambda q, k, v: flash_attention_fn(
        q, k, v, causal=True, window=4096), *qkv)
    assert kernels == {"flash_fwd_band": 1}

    def ffn(x, router, bias, gate_up, down):
        return routed_ffn(x, router, bias, gate_up, down, top_k=4,
                          route_norm=True, route_scale=2.448)[0]

    def compiled(rows):           # a fresh trace: the route is read there
        return jax.jit(lambda *a: ffn(*a)).lower(
            _s(tpu, (rows, 3072)), _s(tpu, (3072, 256)), _s(tpu, (256,)),
            _s(tpu, (32, 3072, 6144)), _s(tpu, (32, 3072, 3072))).compile()

    for rows in (16, 96):
        c = compiled(rows)
        assert program_census(c)["kernels"] == {"grouped_ffn": 1}
        assert c.memory_analysis().temp_size_in_bytes < 16 << 20
    paddle.set_flags({"decode_attention_interpret": False})
    for rows in (16, 96):
        c = compiled(rows)
        assert "grouped_ffn" not in program_census(c)["kernels"]
        assert c.memory_analysis().temp_size_in_bytes < 16 << 20


def test_grouped_ffn_compiles_for_a_chip_of_half_the_vmem(tpu, monkeypatch):
    """On a chip of 64 MiB of VMEM a core (a described v5p) the kernel's
    plan is taken from that capacity: the decode batch's 384 sorted rows
    through one chip's 32 experts at the published widths, and the most
    rows the plan takes, compile under the smaller scoped limit; a
    bucket-512 prefill's 2048 rows (which a 128 MiB plan would take, and
    which overflow this chip's VMEM) are left to XLA's pair."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.obs.cost import program_census
    from paddle_tpu.ops.pallas import grouped_ffn as gf

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5p:2x2x1")
    except Exception as e:
        pytest.skip(f"cannot describe a v5p topology here: {e}")
    monkeypatch.setattr(gf, "_vmem_capacity", lambda: 64 << 20)
    v5p = SingleDeviceSharding(topo.devices[0])
    gu, dn = _s(v5p, (32, 3072, 6144)), _s(v5p, (32, 3072, 3072))
    assert not gf.supported(2048, gu, dn, jnp.bfloat16)
    most = max(r for r in range(128, 2048, 128)
               if gf.supported(r, gu, dn, jnp.bfloat16))
    for rows in (384, most):
        c = jax.jit(gf.grouped_ffn).lower(
            _s(v5p, (rows, 3072)), gu, dn,
            _s(v5p, (32,), jnp.int32)).compile()
        assert program_census(c)["kernels"] == {"grouped_ffn": 1}


def test_latent_serving_programs_compile_at_the_published_widths(
        tpu, decoder_kernels_routed, monkeypatch):
    """A.X-K1's serving programs (models/axk1.py) at the published widths
    — hidden 7168, 64 heads, latent 512, rope 64, one dense layer of 18432
    and one routed layer of 12 held experts of 2048 over a router of 192,
    the vocabulary slice of 20480 — compiled for a v5e from shapes alone
    (no weight is made). The chunk program runs the latent decode kernel
    and the row write once a layer and holds no copy of a layer's latent
    (its temporaries stay under one latent leaf); a bucket-2048 admission
    prefill runs the flash forward once a layer and its temporaries stay
    under 1 GiB (the dense layer's 2048 x 36864 gate|up, the expanded
    keys and values of 64 heads, the routed pairs)."""
    from types import SimpleNamespace

    from paddle_tpu.inference import generate as g
    from paddle_tpu.models.axk1 import AxK1Config, AxK1ForCausalLM
    from paddle_tpu.obs.cost import program_census

    cfg = AxK1Config(num_hidden_layers=2, n_routed_experts=192,
                     experts_held=12, vocab_size=20480, dtype="bfloat16")
    slots, max_len, bucket = 8, 4096, 2048

    def on_chip(tree):
        return jax.tree.map(lambda a: _s(tpu, a.shape, a.dtype), tree)

    shapes = on_chip(jax.eval_shape(
        lambda: g._build_params(AxK1ForCausalLM(cfg), max_len, None)))
    monkeypatch.setattr(g, "_build_params", lambda *a: shapes)
    dec = g.LlamaDecoder(SimpleNamespace(config=cfg), max_len=max_len)
    kc, vc = on_chip(jax.eval_shape(lambda: dec._empty_cache(slots)))
    assert kc[0].shape == (slots, 1, max_len, 512)
    assert vc[0].shape == (slots, 1, max_len, 128)
    logits = _s(tpu, (slots, 20480), jnp.float32)
    ri, rf = _s(tpu, (slots,), jnp.int32), _s(tpu, (slots,), jnp.float32)
    keys, done = _s(tpu, (slots, 2), jnp.uint32), _s(tpu, (slots,), jnp.bool_)
    chunk = dec._ring_chunk_decode._jitted.lower(
        shapes, logits, kc, vc, ri, keys, done, ri, rf, None,
        logits, kc, vc, ri, ri, keys, ri, rf, None,
        steps=16, do_sample=False, top_k=None, top_p=None).compile()
    assert program_census(chunk)["kernels"] == {
        "mla_decode_attention": 2, "kv_row_write": 2, "grouped_ffn": 1}
    # nothing outside the kernels writes a whole latent or rope leaf, and
    # the temporaries are the compiler's re-layouts of weights (q_b's 36
    # MB and kv_b's 16 MB a layer, the rope tables), whatever the cache
    assert _whole_cache_writers(chunk.as_text(), {
        f"{slots},1,{max_len},512", f"{slots},1,{max_len},128"}) == []
    assert chunk.memory_analysis().temp_size_in_bytes < 128 << 20
    one = on_chip(jax.eval_shape(lambda: dec._empty_cache(1)))
    r1 = _s(tpu, (1,), jnp.int32)
    prefill = dec._ring_admit_prefill._jitted.lower(
        shapes, _s(tpu, (1, bucket), jnp.int32), *one, r1, r1, logits, kc,
        vc, r1, None).compile()
    assert program_census(prefill)["kernels"]["flash_fwd"] == 2
    assert prefill.memory_analysis().temp_size_in_bytes < 1 << 30
