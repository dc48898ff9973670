"""Mosaic-compile the serving path's paged kernels and programs, and the training
step's flash kernels, at real widths for a described (not attached) TPU v5e: what the chip's compiler would refuse is
refused here, at no chip time. Interpret mode cannot show a misaligned slice
or a kernel over its VMEM.

The topology is described inside a fixture, never at import: only the worker
that runs this file loads the TPU library (see the on-chip-measurement
guide). Keep such tests in this one file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.serving.kv_cache import Cache


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


SHAPES = {
    # name: (B, H, KV, D, page, n, P, pool dtype)
    "gpt2-xl": (8, 25, 25, 64, 16, 64, 512, jnp.bfloat16),
    # XL as it is served since ISSUE 56: 13 PAIRS of 128 lanes under 26 padded queries
    "gpt2-xl-pairs": (8, 26, 13, 128, 16, 64, 512, jnp.bfloat16),
    "gqa-rep5": (8, 25, 5, 64, 16, 64, 512, jnp.bfloat16),
    "kv64-d128": (8, 64, 64, 128, 16, 64, 512, jnp.bfloat16),
    "xl-tp5-shard": (8, 5, 5, 64, 16, 64, 512, jnp.bfloat16),
    "xl-int8": (8, 25, 25, 64, 32, 32, 256, jnp.int8),
    "head-blocks": (2, 64, 64, 128, 128, 4, 16, jnp.float32),
}
def _default_format(one_chip, shape, dtype):
    """The format the described device gives an array of this shape when
    nothing asks otherwise."""
    pool = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(lambda p: p, donate_argnums=(0,)).lower(pool).compile()
    return compiled.input_formats[0][0]


def _expert_kernel_census(text, layers, E, F, held=16):
    """The held experts' products of a compiled program: ONE grouped kernel an
    expert layer under the name the trace's readers match, no ``ragged-dot``,
    and no copy, slice or transpose whose result is one expert's matrix out of
    the stack or the stack itself (the census the pool tests make, pointed at
    the weights; a bare ``[E, F]`` is the shared expert's shape too, which is
    not this kernel's): the kernel reads the three stacked matrices where
    they lie."""
    from deepspeed_tpu.ops.pallas.grouped_experts import KERNEL_NAME
    from deepspeed_tpu.serving.placement import _relayout_results

    assert re.match(r"moe_+experts_+w_(gate|up|down)", KERNEL_NAME)
    assert len(re.findall(rf"^\s*%?{KERNEL_NAME}[.\d]* = .*custom-call\(", text, re.M)) == layers
    assert "ragged-dot" not in text
    matrices = {(lead, a, b) for a, b in ((E, F), (F, E)) for lead in (1, held)}
    assert [(op, d) for op, _, d in _relayout_results(text) if d in matrices] == []


LAYERS, LAYER = 3, 1  # the serving engine's [L, P, KV, page, D] pool and a layer of it


def _kernel_args(one_chip, name, layered, T=None):
    B, H, KV, D, page, n, P, dtype = SHAPES[name]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = ((LAYERS,) if layered else ()) + (P, KV, page, D)
    q = (B, H, D) if T is None else (B, T, H, D)
    args = [sds(q, jnp.bfloat16), sds(pool, dtype), sds(pool, dtype),
            sds((B, n), jnp.int32), sds((B,), jnp.int32)]
    if dtype == jnp.int8:
        args.append(sds((P, KV, 2), jnp.float32))
    return args


@pytest.mark.parametrize("layered", [False, True], ids=["pool4d", "pool5d"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_paged_decode_kernel_compiles_for_v5e(one_chip, name, layered):
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention,
    )

    def f(q, k, v, bt, pos, scales=None):
        return paged_decode_attention(
            q, k, v, bt, pos, scales=scales, layer=LAYER if layered else None
        )

    compiled = jax.jit(f).lower(*_kernel_args(one_chip, name, layered)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_kernel_compiles_at_its_widest_step_for_v5e(one_chip):
    """ISSUE 50: 2 kv heads of 128-key pages x 128 lanes (ZAYA's served
    shape: 64 slots, 4 query heads a kv head, a table of 48, 14 layers) take
    16 pages a grid step: 32 page inputs for K and 32 for V, 4 MB of page
    buffers, the block's K and V joined and the scores beside them, under
    the default scoped VMEM (the call asks for no more). As the decode
    program calls it: named, the layer an operand of the shared kernel."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention,
        paged_decode_blocks,
    )

    B, H, KV, D, page, n, L = 64, 8, 2, 128, 128, 48, 14
    assert paged_decode_blocks(KV, page, D, 2, n) == (KV, 16)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((L, B * n + 1, KV, page, D), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, bt, pos: paged_decode_attention(
            q, k, v, bt, pos, layer=L - 1, name="decode_fn"
        )
    ).lower(
        sds((B, H, D), jnp.bfloat16), pool, pool, sds((B, n), jnp.int32),
        sds((B,), jnp.int32),
    ).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("B,H,KV,D,page,n,L,blocks,window", [
    (8, 26, 13, 128, 16, 64, 48, (13, 16), False),     # GPT-2-XL's pairs
    (64, 8, 2, 128, 128, 48, 14, (2, 16), False),      # ZAYA's
    (64, 40, 10, 128, 128, 48, 1, (10, 2), False),     # Phi-4-mini-flash's pair heads, its one paged layer
    (64, 64, 8, 128, 16, 224, 3, (8, 32), False),      # K-EXAONE's full layers: 32 page inputs for K, 32 for V
    (64, 64, 8, 128, 16, 10, 9, (8, 8), True),         # ... and its window rings, the keys bounded from below
], ids=["gpt2-xl-pairs", "zaya", "phi4flash-pairs", "kexaone-32-inputs", "kexaone-ring"])
def test_the_paged_decode_kernels_walk_of_a_calls_own_items_compiles_for_v5e(one_chip, B, H, KV, D, page, n, L, blocks, window):
    """ISSUE 58: the one-dimensional grid whose bound is a value of the call
    (the items the live slots' lengths and the idle slots come to) under the
    seven or eight scalar-prefetched vectors, as the served programs call it:
    named, the slots' liveness beside the table, the layer of a deep pool an
    operand of the shared kernel."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention,
        paged_decode_blocks,
    )

    assert paged_decode_blocks(KV, page, D, 2, n) == blocks

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((L, B * n + 1, KV, page, D), jnp.bfloat16)
    lowered = jax.jit(
        lambda q, k, v, bt, pos, live, lo: paged_decode_attention(
            q, k, v, bt, pos, layer=L - 1, name="decode_fn", live=live, lo=lo if window else None
        )
    ).lower(
        sds((B, H, D), jnp.bfloat16), pool, pool, sds((B, n), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.bool_), sds((B,), jnp.int32),
    )
    # the grid's bound goes in as the call's first operand, a scalar, ahead of the prefetched vectors
    assert "operand_layouts = [dense<> : tensor<0xindex>, dense<0> : tensor<1xindex>" in lowered.as_text()
    assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("layered", [False, True], ids=["pool4d", "pool5d"])
@pytest.mark.parametrize("T", [128, 5], ids=["chunk128", "verify5"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_paged_multitoken_kernel_compiles_for_v5e(one_chip, name, T, layered):
    """The chunk-prefill width (128 query tokens) and the verify shape at
    every shape the decode kernel takes: all kv-heads and a block of pages a
    grid step (ISSUE 31), head blocks where ``paged_multitoken_blocks`` says
    so."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_multitoken_attention,
        paged_multitoken_blocks,
    )

    B, H, KV, D, page, n, P, dtype = SHAPES[name]
    blocks = paged_multitoken_blocks(
        KV, page, D, T, jnp.dtype(dtype).itemsize, n, H // KV
    )
    blocked = {("head-blocks", 128): 16, ("head-blocks", 5): 16, ("kv64-d128", 128): 32}
    assert blocks is not None and blocks[0] == blocked.get((name, T), KV)

    def f(q, k, v, bt, base, scales=None):
        return paged_multitoken_attention(
            q, k, v, bt, base, scales=scales, layer=LAYER if layered else None
        )

    args = _kernel_args(one_chip, name, layered, T=T)
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("T", [None, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_paged_token_write_compiles_for_v5e(one_chip, name, T):
    """The decode step's one-token write and the verify step's T tokens a
    slot, in place on both whole pools, at every shape the attention
    kernels take (``head-blocks``: a block of the kv-heads a grid step)."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_token_write,
        paged_token_write_blocks,
    )

    B, H, KV, D, page, n, P, dtype = SHAPES[name]
    HB = paged_token_write_blocks(KV, page, D, jnp.dtype(dtype).itemsize, T or 1)
    blocked = {("head-blocks", None): 4, ("head-blocks", 5): 4, ("kv64-d128", 5): 32}
    assert HB == blocked.get((name, T), KV)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    at = (B,) if T is None else (B, T)
    pool = sds((LAYERS, P, KV, page, D), dtype)
    vals = sds(at + (KV, D), jnp.bfloat16)
    compiled = jax.jit(
        lambda k, v, pidx, poff, kn, vn: paged_token_write(
            k, v, LAYER, pidx, poff, kn, vn),
        donate_argnums=(0, 1),
    ).lower(pool, pool, sds(at, jnp.int32), sds(at, jnp.int32), vals, vals).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("layout", ["pairs", "per_head"])
@pytest.mark.parametrize("program", ["decode", "verify", "chunk", "mixed", "prefill"])
def test_paged_programs_keep_the_pool_in_one_layout(one_chip, program, layout, monkeypatch):
    """ISSUE 29's census: the paged programs at XL width, 512 pages
    and 4 layers, the pools stored as ``kv_cache.pool_stored_shape`` chooses on
    a TPU (this process sees the CPU, so the test hands it over), donated, and
    compiled as the scheduler compiles them (``ProgramSet.aot``, over described
    pools): the device's default layout of such a pool is row-major, no
    instruction copies, slices or transposes a layer of a pool or more, and
    the temp stays under the head's transposed ``wte`` and one layer of K and V
    (a single re-laid pool is four layers of either). ``pairs``: what
    ``GPT2Family`` serves 64-wide heads as (ISSUE 56), 13 cached heads of 128
    lanes in the plain 5-D shape; ``per_head``: a head a published head
    (``GPT2Config.per_head_cache``, what an int8 cache keeps), 25 heads of 64
    with the page axis split."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.kv_cache import pool_stored_shape
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    L, P, H, page, B, W, C, Sp = 4, 512, 25, 16, 8, 64, 128, 960
    cfg = gpt2.GPT2Config(n_embd=H * 64, n_head=H, n_layer=L,
                          attn_impl="pallas", dtype=jnp.bfloat16)
    if layout == "per_head":
        cfg = cfg.per_head_cache()
    fam = cfg.serving_family()
    KV, D = fam.n_kv_head, fam.head_dim
    assert (KV, D) == ((13, 128) if layout == "pairs" else (25, 64))
    # the pool is split, and the token write takes its kernel, where the
    # backend is a TPU: say so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if program == "mixed":
        # as at the served depth of 48: the layers' calls of each kernel share
        # one traced kernel whose layer is a prefetched operand
        from deepspeed_tpu.ops.pallas import decode_attention as da
        monkeypatch.setattr(da, "SHARED_FROM_LAYERS", L)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0))),
    )
    shape = pool_stored_shape(L, P, KV, page, D, jnp.bfloat16)
    assert shape == ((L, P, KV, page, D) if layout == "pairs" else (L, 8, 64, KV, page, D))
    # described as a live pool is: in the device's default format for its shape
    pool = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16)
    )
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(
                cfg, p, tok, lens, c, bt, keys),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "verify": (  # three drafts a slot: one write and four attentions a layer
            lambda p, c, tok, lens, bt: smodel.paged_verify_step(
                cfg, p, tok, lens, c, bt),
            (sds((B, 4), i32), sds((B,), i32), sds((B, W), i32)),
        ),
        "chunk": (
            lambda p, c, ids, start, plen, pages, bt, key:
                smodel.paged_chunk_prefill(
                    cfg, p, ids, start, plen, c, pages, bt, key),
            (sds((1, C), i32), sds((), i32), sds((), i32),
             sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "mixed": (  # what the engine compiles in the chunk program's place (ISSUE 35)
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key:
                smodel.paged_mixed_step(
                    cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32),
             sds((1, C), i32), sds((), i32), sds((), i32),
             sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key: smodel.paged_prefill(
                cfg, p, ids, plen, c, pages, key),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32),
             sds((2,), u32)),
        ),
    }[program]
    # a ProgramSet over DESCRIBED pools (its own __init__ allocates them)
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params,
        cache=Cache(pool, pool),
        _kv_axis=pool.ndim - 3,
        num_pages=P, page_size=page, n_kv_head=KV, head_dim=D, n_layer=L,
    )
    compiled = pset.aot(fn, host, with_params=True)
    text = compiled.as_text()
    assert pset.program_census(program, compiled)[0] == 0  # or it raises
    assert "tpu_custom_call" in text or program == "prefill"
    if program == "decode":  # one attention kernel and one token write a layer
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 2 * L
        assert "dynamic-update-slice(" not in text
    if program == "verify":
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 5 * L
    if program == "mixed":  # both attention kernels and the token write a layer, under their own names
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 3 * L
        for kernel in ("decode_fn", "chunk_fn", "kv_token_write"):
            assert len(re.findall(rf"^\s*%?{kernel}[.\d]* = .*custom-call\(", text, re.M)) == L, kernel
    took_in, _ = compiled.input_formats
    for fmt in (took_in[1].k, took_in[1].v, *compiled.output_formats[0][:2]):
        assert fmt.layout.major_to_minor == tuple(range(len(shape)))
    layer_kv_bytes = 2 * P * KV * page * 128 * 2  # 64 lanes pad to 128
    head_bytes = cfg.padded_vocab_size * cfg.n_embd * 2  # the tied head's transposed ``wte``
    assert compiled.memory_analysis().temp_size_in_bytes < head_bytes + layer_kv_bytes


def _placed_format(one_chip, shape, dtype):
    """The format ``placement.lay_row_major`` leaves an array of this shape in
    on the described device: the device's own where that is row-major, else
    what the one copy it makes comes out in (``jax.device_put`` to a format is
    this identity program; nothing can be put on a described device)."""
    from types import SimpleNamespace as NS

    from deepspeed_tpu.serving.placement import row_major_format

    own = _default_format(one_chip, shape, dtype)
    want = row_major_format(NS(format=own, ndim=len(shape)))
    if want is None:
        return own
    lies = jax.ShapeDtypeStruct(shape, dtype, sharding=own)
    return jax.jit(lambda x: x, out_shardings=want).lower(lies).compile().output_formats


@pytest.mark.parametrize("shape, own, placed", [
    # GPT-2 XL's tables, as published and with the vocabulary padded to whole
    # lane tiles: 1 600 is 12.5 tiles of 128 lanes, so the device lays the
    # VOCABULARY minor, and a gather of rows has to re-lay the table
    ((50257, 1600), (1, 0), (0, 1)),
    ((50304, 1600), (1, 0), (0, 1)),
    ((1024, 1600), (1, 0), (0, 1)),
    # rows of whole lane tiles are row-major as they come: nothing is made
    ((50257, 1664), (0, 1), (0, 1)),
    ((50257, 2048), (0, 1), (0, 1)),
], ids=lambda x: "x".join(map(str, x)))
def test_a_table_whose_rows_are_no_whole_lane_tiles_is_vocabulary_minor_until_placed(one_chip, shape, own, placed):
    """The fact ISSUE 61's rule rests on, and what the rule leaves: the
    device's default order for a bf16 table, and the order of the leaf as
    ``Placement.shard_params`` places a family's ``row_gathered`` leaves."""
    assert _default_layout(one_chip, shape, jnp.bfloat16) == own
    fmt = _placed_format(one_chip, shape, jnp.bfloat16)
    assert tuple(fmt.layout.major_to_minor) == placed
    if own == placed:
        assert fmt == _default_format(one_chip, shape, jnp.bfloat16)


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed", "prefill"])
def test_gpt2_programs_read_the_tables_where_the_placement_laid_them(one_chip, program, monkeypatch):
    """ISSUE 61: the GPT-2 XL served programs (XL's widths, 2 layers), compiled
    as the scheduler compiles them, twice: over the weights as the device
    lays them by default (the parent's) and as ``Placement.shard_params``
    places them (the family's ``row_gathered`` leaves row-major). The first
    copies the whole ``wte`` (161 MB) and ``wpe`` to gather a few rows; the
    second holds no copy, slice or transpose of either's size, takes the
    tables as they lie, and needs at least 150 MB less of temporaries."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.kv_cache import pool_stored_shape
    from deepspeed_tpu.serving.placement import Placement, ProgramSet, _relayout_results

    L, P, H, page, B, W, C, Sp = 2, 512, 25, 16, 8, 64, 128, 960
    cfg = gpt2.GPT2Config(n_embd=H * 64, n_head=H, n_layer=L, attn_impl="pallas", dtype=jnp.bfloat16)
    fam = cfg.serving_family()
    assert fam.row_gathered == ("wte", "wpe")
    KV, D = fam.n_kv_head, fam.head_dim
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt, fmt=one_chip):
        return jax.ShapeDtypeStruct(shape, dt, sharding=fmt)

    abstract = jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    as_they_come = jax.tree.map(lambda x: sds(x.shape, jnp.bfloat16), abstract)
    placed = dict(as_they_come, **{
        name: sds(abstract[name].shape, jnp.bfloat16, _placed_format(one_chip, abstract[name].shape, jnp.bfloat16))
        for name in fam.row_gathered
    })
    shape = pool_stored_shape(L, P, KV, page, D, jnp.bfloat16)
    pool = sds(shape, jnp.bfloat16, _default_format(one_chip, shape, jnp.bfloat16))
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(cfg, p, tok, lens, c, bt, keys),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "chunk": (
            lambda p, c, ids, start, plen, pages, bt, key:
                smodel.paged_chunk_prefill(cfg, p, ids, start, plen, c, pages, bt, key),
            (sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "mixed": (
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key:
                smodel.paged_mixed_step(cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32), sds((1, C), i32), sds((), i32),
             sds((), i32), sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key: smodel.paged_prefill(cfg, p, ids, plen, c, pages, key),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32), sds((2,), u32)),
        ),
    }[program]
    tables = {dims for name in fam.row_gathered for dims in (abstract[name].shape, abstract[name].shape[::-1])}
    table_bytes = 2 * sum(abstract[name].size for name in fam.row_gathered)

    def compiled_over(params):
        pset = object.__new__(ProgramSet)
        pset.__dict__.update(
            placement=Placement("v5e", [one_chip._device], 1), params=params, cache=Cache(pool, pool),
            _kv_axis=pool.ndim - 3, num_pages=P, page_size=page,
            n_kv_head=KV, head_dim=D, n_layer=L,
        )
        exe = pset.aot(fn, host, with_params=True)   # or WeightLayoutError: the program takes a leaf in another order
        sized = [d for _, _, d in _relayout_results(exe.as_text()) if d in tables]
        return exe, sized, pset.program_census(program, exe)[2:], exe.memory_analysis().temp_size_in_bytes

    _, sized, census, parents_temp = compiled_over(as_they_come)
    assert sorted(sized) == [(1024, 1600), (50257, 1600)]
    assert census == (2, table_bytes)
    exe, sized, census, temp = compiled_over(placed)
    assert sized == [] and census == (0, 0)
    assert parents_temp - temp >= 150e6
    for name in fam.row_gathered:
        assert exe.input_formats[0][0][name] == placed[name].format
        assert tuple(placed[name].format.layout.major_to_minor) == (0, 1)


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed", "prefill"])
def test_window_family_programs_compile_at_the_served_size(one_chip, program, monkeypatch):
    """The ``exaone_moe`` programs as the K-EXAONE cell serves them (64 slots,
    64 query heads on 8 kv heads of 128, a paged full layer beside four
    25-page window rings a slot, 16 held experts of 128, 7.4 GB of bf16
    weights as shapes): the paged kernels with a lower bound on the walk and
    the token write into the rings pass Mosaic, nothing re-lays a pool out,
    the whole-prompt program's blocked attention keeps its temps under 1 GB
    (``[64, 3072, 3072]`` float32 scores would be 2.4 GB a layer), and
    arguments + temps fit the chip."""
    import json

    from deepspeed_tpu.models import exaone_moe
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    with open(os.path.join(root, "perfbench", "configs", "k-exaone-236b-ep8-serve-1chip.json")) as f:
        c = json.load(f)
    cfg = exaone_moe.ExaoneMoEConfig.from_dict(c)
    sv = c["serving"]
    B, page, P, Sp, C = (sv[k] for k in ("max_slots", "page_size", "num_pages", "max_prompt_len", "prefill_chunk_tokens"))
    W = -(-(Sp + sv["max_new_tokens"]) // page)
    ring = -(-(cfg.sliding_window + C) // page) + 1
    KV, D = cfg.n_kv_head, cfg.head_dim
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: exaone_moe.init_params(cfg, jax.random.PRNGKey(0))),
    )
    assert 7.4e9 < 2 * sum(x.size for x in jax.tree.leaves(params)) < 7.45e9
    # described as live pools are: in the device's default format for their shape
    pool, wpool = (
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16))
        for shape in ((1, P, KV, page, D), (4, 1 + B * ring, KV, page, D))
    )
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(
                cfg, p, tok, lens, c, bt, keys, ring=ring),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "chunk": (
            lambda p, c, ids, start, plen, pages, bt, key, slot:
                smodel.paged_chunk_prefill(
                    cfg, p, ids, start, plen, c, pages, bt, key,
                    slot=slot, ring=ring),
            (sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32),
             sds((1, W), i32), sds((2,), u32), sds((), i32)),
        ),
        "mixed": (
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key, slot:
                smodel.paged_mixed_step(
                    cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key,
                    slot=slot, ring=ring),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32),
             sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32),
             sds((1, W), i32), sds((2,), u32), sds((), i32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key, slot: smodel.paged_prefill(
                cfg, p, ids, plen, c, pages, key, slot=slot, ring=ring),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32), sds((2,), u32),
             sds((), i32)),
        ),
    }[program]
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params,
        cache=Cache(pool, pool, None, wpool, wpool),
        _kv_axis=2, num_pages=P, page_size=page, n_kv_head=KV, head_dim=D, n_layer=1,
    )
    compiled = pset.aot(fn, host, with_params=True)
    text = compiled.as_text()
    assert pset.program_census(program, compiled)[0] == 0  # or it raises
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    # an attention kernel a layer, in the decode step a token write a layer,
    # and the grouped kernel an expert layer
    sparse = len(exaone_moe.ExaoneFamily(cfg).sparse_layers)
    assert calls == {"decode": 10, "chunk": 5, "mixed": 15, "prefill": 0}[program] + sparse
    _expert_kernel_census(text, sparse, cfg.n_embd, c["moe_intermediate_size"])
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10e9   # of the chip's 16


def _default_layout(one_chip, shape, dtype):
    return tuple(_default_format(one_chip, shape, dtype).layout.major_to_minor)


@pytest.mark.parametrize("geometry", [
    # (L, P, KV, page, D, dtype): the served cells, larger pools (ROADMAP R0),
    # a tensor-parallel shard, int8 pages (R10), a pool of many small pages,
    # and the 128-wide heads of queue R (OLMoE, Trinity-Mini), stored plainly
    (48, 512, 25, 16, 64, jnp.bfloat16),
    (48, 1024, 25, 16, 64, jnp.bfloat16),
    (12, 4096, 12, 16, 64, jnp.bfloat16),
    (12, 8192, 12, 16, 64, jnp.bfloat16),
    (48, 1000, 5, 16, 64, jnp.bfloat16),
    (48, 256, 25, 32, 64, jnp.int8),
    (48, 512, 25, 32, 64, jnp.int8),
    (16, 512, 16, 16, 128, jnp.bfloat16),
    (16, 4096, 16, 16, 128, jnp.bfloat16),
    (32, 2048, 4, 16, 128, jnp.bfloat16),
    (27, 512, 16, 32, 128, jnp.int8),
], ids=lambda g: "x".join(map(str, g[:5])) + "-" + jnp.dtype(g[5]).name)
def test_stored_pool_shapes_are_row_major_by_default(one_chip, monkeypatch, geometry):
    """``kv_cache.pool_stored_shape`` makes the pool row-major by its shape
    alone: the device's default layout of what it returns keeps every axis in
    its place, at the page counts and widths the cells and the roadmap's
    next ones use."""
    from deepspeed_tpu.serving.kv_cache import PAGE_GROUP, pool_stored_shape

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = pool_stored_shape(*geometry)
    if geometry[4] % 128:
        assert max(shape[1:-3]) <= PAGE_GROUP and len(shape) > 5
    else:
        assert len(shape) == 5
    assert _default_layout(one_chip, shape, geometry[5]) == tuple(range(len(shape)))


@pytest.mark.parametrize("shape, moved", [
    # the page axis whole, as the parent stored it: pages minor-most
    ((4, 512, 25, 16, 64), (0, 2, 3, 4, 1)),
    # what pool_stored_shape cannot arrange, and ProgramSet refuses on the
    # chip: a prime page count, an axis just over the head's width, more
    # kv-heads or layers than the head is wide
    ((4, 509, 25, 16, 64), (0, 2, 3, 4, 1)),
    ((4, 65, 8, 25, 16, 64), (0, 2, 3, 4, 5, 1)),
    ((4, 8, 64, 96, 16, 64), (0, 1, 2, 4, 5, 3)),
    ((100, 8, 64, 5, 16, 64), (1, 2, 3, 4, 5, 0)),
], ids=lambda x: "x".join(map(str, x)))
def test_an_axis_longer_than_a_narrow_head_is_moved(one_chip, shape, moved):
    """Why the pool is stored split, and where the split ends: with a
    64-wide head the device's default layout puts an axis longer than the
    head minor-most, whichever axis it is."""
    assert _default_layout(one_chip, shape, jnp.bfloat16) == moved


# -- the training step's flash kernels under the plan the rule picks (ISSUE 33) --

FLASH_SHAPES = {
    # name: (B*H, S, D, q heads to a kv head); bf16
    "train-cells": (100, 1024, 64, 1),      # [4, 1024, 25, 64] a chip, both cells
    "d128": (64, 1024, 128, 1),
    "gqa-rep4": (64, 1024, 128, 4),
    "gqa-rep8-d64": (96, 1024, 64, 8),
    "s2048": (50, 2048, 64, 1),             # the longest whole-sequence grid step
    "s2048-d128": (32, 2048, 128, 1),       # forward whole, backward a piece a step
    "s4096-d128": (16, 4096, 128, 1),       # the backward's VMEM leaves 256 x 256
    "s512": (200, 512, 64, 1),
}


@pytest.mark.parametrize("mode", ["causal", "window", "full"])
@pytest.mark.parametrize("name", list(FLASH_SHAPES))
def test_flash_kernels_compile_for_v5e_under_their_plan(one_chip, name, mode):
    """The resident forward and the fused backward at the plan ``flash_plan``
    gives the shape: interpret mode cannot show a tile over VMEM, a walk
    written out past the kernel's stack, or a misaligned slice. ``window``
    is a traced window operand (GPT-Neo's local layers), ``full`` the
    non-causal kernels (bidirectional, Ulysses, the ring's off-diagonal)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    BH, S, D, rep = FLASH_SHAPES[name]
    causal = mode != "full"
    assert fa.resident_ok(S, D, 2) and fa._fused_bwd_ok(S, D, rep)
    plans = [fa.flash_plan(S, D, 2, rep, backward=b, causal=causal) for b in (False, True)]
    assert all(S % b == 0 and b > 128 for plan in plans for b in plan), plans
    if name == "train-cells" and causal:
        assert plans == [(1024, 512)] * 2      # what the census found fastest there

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, kv = sds((BH, S, D)), sds((BH // rep, S, D))
    win = [sds((1,), jnp.int32)] if mode == "window" else []

    def fwd(q, k, v, *w):
        return fa._fwd(q, k, v, 0.125, causal, False, rep, *w)

    def bwd(q, k, v, o, lse, do, *w):
        return fa._bwd(q, k, v, o, lse, do, 0.125, causal, False, rep, *w)

    for f, args in ((fwd, [q, kv, kv]),
                    (bwd, [q, kv, kv, q, sds((BH, S, fa.NUM_LANES), jnp.float32), q])):
        compiled = jax.jit(f).lower(*args, *win).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


# -- telemetry.parts: a scope names no kernel, and every kernel has a part ------------------

def _xl_train_step(one_chip):
    """Two XL-wide layers of the training step's forward, backward and update
    under full remat, the flash kernels forced: its optimised text."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.telemetry import parts

    cfg = gpt2.GPT2Config(n_embd=1600, n_head=25, n_layer=2, n_positions=1024, attn_impl="pallas",
                          dtype=jnp.bfloat16, remat=True)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, jnp.bfloat16),
                          jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0))))

    def train_step(p, ids):
        loss, grads = jax.value_and_grad(lambda p: gpt2.lm_loss(cfg, p, {"input_ids": ids}, None, True)[0])(p)
        with parts.part("optim"):
            return loss, jax.tree.map(lambda a, g: a - 0.01 * g.astype(a.dtype), p, grads)

    return jax.jit(train_step).lower(params, sds((2, 1024), jnp.int32)).compile().as_text()


def _xl_decode_step(one_chip):
    """The decode step at XL width over plain 5-D pools (13 pairs of 128
    lanes; its kernels are the ones the package gives no name): its optimised
    text."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.serving import model as smodel

    L, P, KV, page, D, B, W = 2, 512, 13, 16, 128, 8, 64
    cfg = gpt2.GPT2Config(n_embd=1600, n_head=25, n_layer=L, attn_impl="pallas", dtype=jnp.bfloat16)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, jnp.bfloat16),
                          jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0))))
    pool = sds((L, P, KV, page, D), jnp.bfloat16)

    def decode_fn(p, c, tok, lens, bt, keys):
        return smodel.paged_decode_step(cfg, p, tok, lens, c, bt, keys)

    return jax.jit(decode_fn, donate_argnums=(1,)).lower(
        params, Cache(pool, pool), sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B, W), jnp.int32),
        sds((B, 2), jnp.uint32)).compile().as_text()


@pytest.mark.parametrize("program, kernels", [
    ("train", {"closed_call": ("attn.core", "fwd"), "rematted_computation": ("attn.core", "recompute"),
               "checkpoint": ("attn.core", "bwd")}),
    ("decode", {"decode_fn": ("attn.core", "none"), "kv_token_write": ("kv.write", "none")}),
])
def test_scopes_rename_no_kernel_and_every_kernel_has_a_part(one_chip, monkeypatch, program, kernels):
    """ISSUE 36. XLA names an unnamed kernel's custom call after the innermost
    scope open around it, and the benchmark's patterns know the kernels by the
    names they have: with the ``dspart.*`` scopes in the program the compiled
    text is, metadata apart, the text without them, the kernels keep their
    names, and the part table gives each its part and its pass (the flash
    kernels' through the hole around their ``custom_vjp`` call)."""
    import contextlib

    from deepspeed_tpu.telemetry import parts

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # the token write takes its kernel
    build = {"train": _xl_train_step, "decode": _xl_decode_step}[program]
    scoped = build(one_chip)
    with monkeypatch.context() as m:
        m.setattr(parts, "part", lambda name: contextlib.nullcontext())
        null = build(one_chip)

    def strip(text):
        # (a kernel's serialised body is not the same bytes from one lowering to the next)
        text = re.sub(r'"body":"[^"]*"', "", text[text.index("\n\n", text.index("StackFrames")):])
        return re.sub(r",? ?metadata=\{[^}]*\}", "", text)

    assert "dspart." in scoped and "dspart." not in null
    assert strip(scoped) == strip(null)
    table = parts.table_of(scoped)
    found = {}
    for name in re.findall(r"^\s*%([\w.\-]+) = .*custom-call\(.*tpu_custom_call", scoped, re.M):
        e = table[name]
        assert e.has_dot
        found[re.sub(r"[.\d]+$", "", name)] = (e.part, e.phase)
    assert found == kernels
    dots = [e for e in table.values() if e.has_dot]
    assert all(e.part for e in dots) and {e.part for e in dots} >= {"attn.qkv", "attn.out", "mlp", "head"}


def test_the_v5e_compiler_takes_the_schedule_the_train_step_asks_for(topo, one_chip):
    """ISSUE 45. The train step names the module scheduler whose order keeps
    the backward loop's carried gradient in place (``DeepSpeedEngine.
    _step_compiler_options``): the option is libtpu's own, so the compiler of
    the described v5e has to know it under that name, and a CPU mesh passes
    nothing."""
    from types import SimpleNamespace

    import numpy as np

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    def options(device):
        mesh = SimpleNamespace(devices=np.array([device], dtype=object))
        policy = SimpleNamespace(gathers_params_in_step=lambda: False)   # one chip: no parameter is gathered
        return DeepSpeedEngine._step_compiler_options(SimpleNamespace(mesh=mesh, policy=policy))

    assert options(jax.devices("cpu")[0]) is None
    asked = options(topo.devices[0])
    assert asked == {"xla_memory_scheduler": "dfs"}
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    lowered = jax.jit(lambda a: (a @ a).sum()).lower(x)
    assert lowered.compile(compiler_options=asked).as_text()
    with pytest.raises(Exception, match="xla_memory_scheduler_"):
        lowered.compile(compiler_options={"xla_memory_scheduler_": "dfs"})


# -- the latent (MLA) family: kernels, pool layout and programs at the served size --

def _ms4_config():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    with open(os.path.join(root, "perfbench", "configs", "mistral-small-4-119b-ep8-serve-1chip.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("B,T", [(48, 1), (1, 1024)], ids=["decode", "chunk"])
def test_latent_kernels_compile_for_v5e_at_the_served_shapes(one_chip, B, T):
    from deepspeed_tpu.ops.pallas.latent_attention import latent_paged_attention, latent_token_write

    c = _ms4_config()
    sv = c["serving"]
    L, P, page, W = c["num_hidden_layers"], sv["num_pages"], sv["page_size"], 384
    n = -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // page)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((L, P, 1, page, W), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, p, bt, base: latent_paged_attention(q, p, bt, base, 256, 0.195, layer=3, name="mla")
    ).lower(sds((B, T, 32, W), jnp.bfloat16), pool, sds((B, n), jnp.int32), sds((B,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    if T == 1:
        compiled = jax.jit(
            lambda p, pidx, poff, rows: latent_token_write(p, 3, pidx, poff, rows), donate_argnums=(0,)
        ).lower(pool, sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B, 1, W), jnp.bfloat16)).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6      # the pool is written in place


@pytest.mark.parametrize("ctx,steps", [(6144, 432), (12288, 816), (23552, 1520)])
def test_the_latent_chunk_kernels_grid_is_the_calls_own_walk(ctx, steps):
    """At ``mistral4``'s chunk shape (1 024 queries in 32 query blocks, 512
    keys a step, a table of 194 pages) the grid bound the wrapper hands the
    kernel is the (query block, page block) pairs the call owns, ``ctx / 16 +
    48``, where the rectangle was 32 x 49 = 1 568 steps at any context; the
    host's rule for the counters reckons the same."""
    import numpy as np

    from deepspeed_tpu.ops.pallas import latent_attention as la

    c = _ms4_config()
    sv = c["serving"]
    page, T, H = sv["page_size"], sv["prefill_chunk_tokens"], c["num_attention_heads"]
    n = -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // page)
    TQ, G = la.latent_blocks(H, page, T, n)
    assert (TQ, G, n) == (32, 4, 194)
    bt = jnp.arange(n, dtype=jnp.int32)[None]
    row, blk, at, pages, n_items = la._walk_items(bt, jnp.asarray([ctx], jnp.int32), T, TQ, G, page)
    assert int(n_items[0]) == steps == ctx // 16 + 48
    assert la.latent_walk_steps([ctx], H, page, T, n) == (steps, 1568)
    # the items: each query block's blocks in a row from 0, the pages the table's ...
    row, blk, at = (np.asarray(x)[:steps] for x in (row, blk, at))
    pages = np.asarray(pages)[: steps * G]
    assert (np.diff(row) >= 0).all() and row[0] == 0 and row[-1] == T // TQ - 1
    assert (blk[np.r_[True, np.diff(row) > 0]] == 0).all()
    assert (blk[1:][np.diff(row) == 0] == blk[:-1][np.diff(row) == 0] + 1).all()
    assert (at == ctx + row * TQ).all() and (blk == np.minimum(blk, (at + TQ - 1) // (G * page))).all()
    # ... but past the last page the query block reaches the page an input held a block ago: no fetch
    e, reach = blk[:, None] * G + np.arange(G), ((at + TQ - 1) // page)[:, None]
    assert (pages.reshape(steps, G) == np.clip(np.where(e > reach, e - G, e), 0, reach)).all()
    assert (e > reach).sum() == 48      # 1.5 pages a query block on average, of the chunk's own 8


def test_a_latent_pool_is_row_major_only_with_whole_lane_tiles_a_row(one_chip, monkeypatch):
    """What ``pool_stored_shape``'s third case rests on: at 320 lanes the
    default layout moves the page index (page 16) or the page's row axis
    (page 128) minor-most; at 384 it is row-major."""
    from deepspeed_tpu.serving.kv_cache import pool_stored_shape

    assert _default_layout(one_chip, (6, 9313, 1, 128, 384), jnp.bfloat16) == (0, 1, 2, 3, 4)
    assert _default_layout(one_chip, (6, 9313, 1, 128, 320), jnp.bfloat16) == (0, 1, 2, 4, 3)
    assert _default_layout(one_chip, (6, 74497, 1, 16, 320), jnp.bfloat16) == (0, 2, 3, 4, 1)
    assert pool_stored_shape(6, 9313, 1, 128, 320, jnp.bfloat16, latent=True) == (6, 9313, 1, 128, 320)  # off the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pool_stored_shape(6, 9313, 1, 128, 320, jnp.bfloat16, latent=True) == (6, 9313, 1, 128, 384)


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed", "prefill"])
def test_latent_family_programs_compile_at_the_served_size(one_chip, program, monkeypatch):
    """The ``mistral4`` programs as the long-document cell serves them (48
    slots, 32 query heads on one 384-lane latent row a token, 128-token pages,
    a 24 576-token whole-prompt width, 16 held experts of 128, 5.75 GB of bf16
    weights as shapes): the latent kernels and the one-pool token write pass
    Mosaic, nothing re-lays the pool out or an expert matrix, the whole-prompt
    program's blocked attention and blocked expert products keep its temps
    beside the pool, and arguments + temps fit the chip."""
    from deepspeed_tpu.models import mistral4
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    c = _ms4_config()
    cfg = mistral4.Mistral4Config.from_dict(c)
    sv = c["serving"]
    B, page, P, Sp, C = (sv[k] for k in ("max_slots", "page_size", "num_pages", "max_prompt_len", "prefill_chunk_tokens"))
    W = -(-(Sp + sv["max_new_tokens"]) // page)
    L = cfg.n_layer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: mistral4.init_params(cfg, jax.random.PRNGKey(0))),
    )
    assert 5.7e9 < 2 * sum(x.size for x in jax.tree.leaves(params)) < 5.8e9
    shape = (L, P, 1, page, 384)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16))
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(cfg, p, tok, lens, c, bt, keys),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "chunk": (
            lambda p, c, ids, start, plen, pages, bt, key: smodel.paged_chunk_prefill(
                cfg, p, ids, start, plen, c, pages, bt, key),
            (sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "mixed": (
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key: smodel.paged_mixed_step(
                cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32),
             sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key: smodel.paged_prefill(cfg, p, ids, plen, c, pages, key),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32), sds((2,), u32)),
        ),
    }[program]
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params, cache=Cache(pool),
        _kv_axis=2, num_pages=P, page_size=page, n_kv_head=1, head_dim=384, n_layer=L,
    )
    compiled = pset.aot(fn, host, with_params=True)
    text = compiled.as_text()
    assert pset.program_census(program, compiled)[0] == 0  # or it raises
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    # an attention kernel a layer, in the decode step a token write a layer,
    # and the grouped kernel an expert layer (every layer here)
    assert calls == {"decode": 3 * L, "chunk": 2 * L, "mixed": 4 * L, "prefill": L}[program]
    _expert_kernel_census(text, L, c["hidden_size"], c["moe_intermediate_size"])
    if program == "mixed":  # the kernels keep the names the readers find them by
        for kernel in ("mla_paged_decode", "mla_paged_chunk", "kv_token_write"):
            assert len(re.findall(rf"^\s*%?{kernel}[.\d]* = .*custom-call\(", text, re.M)) == L, kernel
    took_in, _ = compiled.input_formats
    assert took_in[1].k.layout.major_to_minor == (0, 1, 2, 3, 4) == compiled.output_formats[0].k.layout.major_to_minor
    mem = compiled.memory_analysis()
    print(program, "argument", mem.argument_size_in_bytes, "temp", mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.2e9   # of the chip's 16


# -- the double-layer latent family (LongCat-Flash): the same kernels at 640 lanes, 512 values, 64 heads --

def _lcf_config():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    with open(os.path.join(root, "perfbench", "configs", "longcat-flash-560b-ep32-serve-1chip.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("B,T", [(64, 1), (1, 256), (64, 4)], ids=["decode", "chunk", "verify4"])
def test_latent_kernels_compile_for_v5e_at_the_double_layer_familys_shapes(one_chip, B, T):
    """64 heads on a 576-wide row stored in 640 lanes whose first 512 are the
    values: the blocks come from the shapes (``latent_blocks``: 16 tokens x 64
    heads a chunk step against 512 keys, 2 048 keys a decode step) and fit the
    kernels' VMEM budget as they are."""
    from deepspeed_tpu.ops.pallas.latent_attention import latent_blocks, latent_paged_attention, latent_token_write
    from deepspeed_tpu.serving.kv_cache import pool_stored_shape

    c = _lcf_config()
    sv = c["serving"]
    L, P, page, W = 2 * c["num_layers"], sv["num_pages"], sv["page_size"], 640
    n = -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // page)
    assert latent_blocks(64, page, T, n) == {1: (1, 16), 256: (16, 4), 4: (4, 16)}[T]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((L, P, 1, page, W), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, p, bt, base: latent_paged_attention(q, p, bt, base, 512, 0.0722, layer=5, name="mla")
    ).lower(sds((B, T, 64, W), jnp.bfloat16), pool, sds((B, n), jnp.int32), sds((B,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    if T != 256:
        idx = (B,) if T == 1 else (B, T)
        rows = (B, 1, W) if T == 1 else (B, T, 1, W)
        compiled = jax.jit(
            lambda p, pidx, poff, rows: latent_token_write(p, 5, pidx, poff, rows), donate_argnums=(0,)
        ).lower(pool, sds(idx, jnp.int32), sds(idx, jnp.int32), sds(rows, jnp.bfloat16)).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6      # the pool is written in place
    assert pool_stored_shape(8, 1793, 1, 128, 576, jnp.bfloat16, latent=True) == (8, 1793, 1, 128, 576)  # off the TPU


@pytest.mark.parametrize("program", ["decode", "mixed", "prefill"])
def test_double_layer_latent_family_programs_compile_at_the_served_size(one_chip, program, monkeypatch):
    """The ``longcat_flash`` programs as its cell serves them (64 slots, 8
    cached sub-blocks of 64 query heads on one 640-lane row a token, 128-token
    pages, a 3 072-token whole-prompt width, 16 held experts of 512 + 256
    identity columns, 10.35 GB of bf16 weights as shapes): a latent kernel and
    a token write a SUB-BLOCK, nothing re-lays the pool out, every call of the
    expert layer the grouped kernel, and arguments + temps fit the chip."""
    from deepspeed_tpu.models import longcat_flash
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.kv_cache import pool_stored_shape
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    c = _lcf_config()
    cfg = longcat_flash.LongcatFlashConfig.from_dict(c)
    sv = c["serving"]
    B, page, P, Sp, C = (sv[k] for k in ("max_slots", "page_size", "num_pages", "max_prompt_len", "prefill_chunk_tokens"))
    W = -(-(Sp + sv["max_new_tokens"]) // page)
    L = cfg.n_layer
    assert L == 8 and P == B * W + 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: longcat_flash.init_params(cfg, jax.random.PRNGKey(0))),
    )
    assert 10.3e9 < 2 * sum(x.size for x in jax.tree.leaves(params)) < 10.4e9
    shape = pool_stored_shape(L, P, 1, page, cfg.kv_width, jnp.bfloat16, latent=True)
    assert shape == (L, P, 1, page, 640)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16))
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(cfg, p, tok, lens, c, bt, keys),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "mixed": (
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key: smodel.paged_mixed_step(
                cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32),
             sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32), sds((1, W), i32), sds((2,), u32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key: smodel.paged_prefill(cfg, p, ids, plen, c, pages, key),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32), sds((2,), u32)),
        ),
    }[program]
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params, cache=Cache(pool),
        _kv_axis=2, num_pages=P, page_size=page, n_kv_head=1, head_dim=640, n_layer=L,
    )
    compiled = pset.aot(fn, host, with_params=True)
    text = compiled.as_text()
    assert pset.program_census(program, compiled)[0] == 0  # or it raises
    _expert_kernel_census(text, L // 2, c["hidden_size"], c["expert_ffn_hidden_size"])   # one a DOUBLE layer
    if program != "prefill":  # the kernels keep the names the readers find them by, one a sub-block
        names = {"decode": ("mla_paged_decode", "kv_token_write"),
                 "mixed": ("mla_paged_decode", "mla_paged_chunk", "kv_token_write")}[program]
        for kernel in names:
            assert len(re.findall(rf"^\s*%?{kernel}[.\d]* = .*custom-call\(", text, re.M)) == L, kernel
    took_in, _ = compiled.input_formats
    assert took_in[1].k.layout.major_to_minor == (0, 1, 2, 3, 4) == compiled.output_formats[0].k.layout.major_to_minor
    mem = compiled.memory_analysis()
    print(program, "argument", mem.argument_size_in_bytes, "temp", mem.temp_size_in_bytes)
    assert 12.6e9 < mem.argument_size_in_bytes < 12.8e9     # 10.35 GB of weights and 2.35 GB of pool
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.2e9   # of the chip's 16


# -- the held experts' grouped kernel at the three MoE cells' served shapes (ISSUE 42) --

EXPERT_SHAPES = {
    # name: (E, F, top_k, n_experts, n_zero, rows)
    "kx-decode": (6144, 2048, 8, 128, 0, 64),
    "kx-mixed": (6144, 2048, 8, 128, 0, 320),
    "lcf-decode": (6144, 2048, 12, 512, 256, 64),
    "lcf-mixed": (6144, 2048, 12, 512, 256, 320),
    "ms4-mixed": (4096, 2048, 4, 128, 0, 1072),
    "lcf-whole-prompt-block": (6144, 2048, 12, 512, 256, 768),
    "ms4-whole-prompt-block": (4096, 2048, 4, 128, 0, 1024),
}


@pytest.mark.parametrize("name", list(EXPERT_SHAPES))
def test_grouped_expert_kernel_compiles_for_v5e_within_the_vmem_it_asks(one_chip, name):
    """``held_experts_grouped`` (the sort, the tile map, the kernel, the
    combine) with 16 held experts as shapes: Mosaic takes the kernel at its
    own ``vmem_limit_bytes``, which stays under the core's 128 MiB with room,
    the weight blocks are megabytes (the largest the call's rows leave room for), and the three stacked matrices go in as
    they are: no copy, slice or transpose of an expert matrix."""
    from deepspeed_tpu.moe import expert_share as es
    from deepspeed_tpu.ops.pallas import grouped_experts as ge

    E, F, k, n_experts, n_zero, T = EXPERT_SHAPES[name]
    share = es.ExpertShare(n_experts, n_experts // 16, 0, n_zero)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(
        lambda u, idx, w, wg, wu, wd: es.held_experts_grouped(u, idx, w, share, wg, wu, wd)
    ).lower(
        sds((T, E), jnp.bfloat16), sds((T, k), jnp.int32), sds((T, k), jnp.float32),
        sds((16, E, F), jnp.bfloat16), sds((16, E, F), jnp.bfloat16), sds((16, F, E), jnp.bfloat16),
    ).compile()
    _expert_kernel_census(compiled.as_text(), 1, E, F)
    tm = ge.row_tile(T * k, n_experts + n_zero)
    assert tm == {"kx-decode": 16, "kx-mixed": 64, "lcf-decode": 16, "lcf-mixed": 16, "ms4-mixed": 128,
                  "lcf-whole-prompt-block": 32, "ms4-whole-prompt-block": 64}[name]
    bf = ge.f_block(T, tm, E, F, 2)
    assert bf == (512 if name == "lcf-whole-prompt-block" else 1024) and E * bf * 2 >= 6 << 20   # megabytes a block, not kilobytes
    assert ge.vmem_bytes(T, tm, E, bf, 2) <= ge.VMEM_BYTES < 128 << 20
    assert es.block_rows(jax.ShapeDtypeStruct((T, E), jnp.bfloat16)) == T


# -- ZeRO-3 over dp on the four described chips: the collectives are the weights' (ISSUE 40) --

@pytest.mark.parametrize("pinned", [True, False])
def test_zero3_over_four_chips_gathers_weights_and_reduce_scatters_gradients(topo, monkeypatch, pinned):
    """The training step's forward and backward for the described 2x2 under
    ZeRO-3's parameter and gradient specs (4 layers x 512 wide, 16 x 256
    tokens, bf16, full remat, the flash kernels in their ``shard_map``). With
    the residual stream pinned to the batch axis the loop bodies hold no
    all-to-all and no collective shaped like the global batch's activations:
    weight all-gathers, ONE ``kCustom`` fusion that calls
    ``%all-reduce-scatter`` over three weight gradients, and at most one
    weight-shaped all-reduce (the one the combiner merged with the small
    leaves' before the reduce-scatters were made: ``c_attn_w``'s at this
    width). Without the pin (the parent's program) the partitioner runs the
    layer tensor-parallel over dp: five all-to-alls and the global batch
    gathered."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.zero.partitioning import ZeroShardingPolicy
    from deepspeed_tpu.telemetry.introspect import loop_collectives

    if not pinned:
        monkeypatch.setattr(gpt2, "on_batch_axis", lambda x, axis="dp": x)
    L, E, H, B, S = 4, 512, 8, 16, 256
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    cfg = gpt2.GPT2Config(n_embd=E, n_head=H, n_layer=L, n_positions=S, attn_impl="pallas", dtype=jnp.bfloat16,
                          remat=True)
    mod = gpt2.make_module(cfg)
    abstract = jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    policy = ZeroShardingPolicy(mesh, stage=3)
    grad_specs = policy.grad_shardings(abstract, mod.logical_axes)
    params = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
                          abstract, policy.param_shardings(abstract, mod.logical_axes))
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec("dp")))

    def step(p, ids):
        loss, grads = jax.value_and_grad(lambda p: mod.loss_fn(p, {"input_ids": ids}, None, True)[0])(p)
        return loss, jax.lax.with_sharding_constraint(grads, grad_specs)

    with jax.set_mesh(mesh):
        text = jax.jit(step).lower(params, ids).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3   # the flash kernels are there
    found = loop_collectives(text)
    kinds = {k: [c for c in found if c.kind == k] for k in ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")}
    activations = [c for c in found if c.carries(B * S)]
    if not pinned:
        assert len(kinds["all_to_all"]) == 5
        assert any(dims == (B, S, E) for c in activations if c.kind == "all_gather" for _, dims in c.shapes)
        return
    assert kinds["all_to_all"] == [] and activations == []
    gathered = {dims for c in kinds["all_gather"] for _, dims in c.shapes}
    assert gathered >= {(1, E, 3 * E), (1, E, E), (1, E, 4 * E), (1, 4 * E, E)}, gathered
    # each chip's quarter of three weight gradients, in one fusion
    (scatter,) = kinds["reduce_scatter"]
    assert sorted(dims for _, dims in scatter.shapes) == [(E // 4, E), (E, E), (E, E)], scatter
    assert '%all-reduce-scatter' in text
    weight_shaped = [c for c in kinds["all_reduce"] if any(len(dims) >= 2 for _, dims in c.shapes)]
    assert len(weight_shaped) <= 1, weight_shaped


def test_zero3_over_four_chips_asks_for_a_layers_weights_one_layer_ahead(topo):
    """ISSUE 51. The same step under the options ``DeepSpeedEngine.
    _step_compiler_options`` adds where the policy says that the step gathers
    its parameters: the described v5e's compiler knows all four by name, and
    its collective pipeliner then hands EVERY weight gather of the two layer
    loops on in the loop's state (``LoopCollective.carried``: iteration n
    gathers iteration n+1's slices), where without them none is and each
    stands in front of its own layer's products. The backward loop's
    recompute and transpose share one gathered ``c_attn_w``: a gather fewer.
    The layer in use and the layer being gathered are live together, with
    their re-laid copies: 53 MB more here, 0.22 GB of 3.24 at GPT-2-XL's width
    and depth (PERF.md section 6, PR 51)."""
    from types import SimpleNamespace

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.zero.partitioning import ZeroShardingPolicy
    from deepspeed_tpu.telemetry.introspect import loop_collectives

    L, E, H, B, S = 4, 512, 8, 16, 256
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    cfg = gpt2.GPT2Config(n_embd=E, n_head=H, n_layer=L, n_positions=S, attn_impl="pallas", dtype=jnp.bfloat16,
                          remat=True)
    mod = gpt2.make_module(cfg)
    abstract = jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    policy = ZeroShardingPolicy(mesh, stage=3)
    grad_specs = policy.grad_shardings(abstract, mod.logical_axes)
    params = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
                          abstract, policy.param_shardings(abstract, mod.logical_axes))
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec("dp")))

    def step(p, ids):
        loss, grads = jax.value_and_grad(lambda p: mod.loss_fn(p, {"input_ids": ids}, None, True)[0])(p)
        return loss, jax.lax.with_sharding_constraint(grads, grad_specs)

    with jax.set_mesh(mesh):
        lowered = jax.jit(step).lower(params, ids)
    asked = DeepSpeedEngine._step_compiler_options(SimpleNamespace(mesh=mesh, policy=policy))
    assert len(asked) == 5 and asked["xla_memory_scheduler"] == "dfs"
    below = DeepSpeedEngine._step_compiler_options(SimpleNamespace(mesh=mesh, policy=ZeroShardingPolicy(mesh, stage=2)))
    assert below == {"xla_memory_scheduler": "dfs"}

    def gathers(options):
        compiled = lowered.compile(compiler_options=options)
        found = [c for c in loop_collectives(compiled.as_text()) if c.kind == "all_gather"]
        assert found and not any(c.carries(B * S) for c in found)
        return found, compiled.memory_analysis().temp_size_in_bytes

    parents, parents_temp = gathers(below)
    ahead, temp = gathers(asked)
    assert not any(c.carried for c in parents)
    assert all(c.carried for c in ahead), [(c.name, c.shapes) for c in ahead if not c.carried]
    assert len(ahead) == len(parents) - 1
    assert sum(c.ahead for c in ahead) >= sum(c.ahead for c in parents)
    assert temp - parents_temp < 64e6   # (reads 53 MB of 261)


# -- the recurrent family (phi4flash): the scan kernels and the three programs at the served size (ISSUE 43) --

@pytest.mark.parametrize("entry,rows", [("chunk", 256), ("chunk", 2048), ("chunk", 37), ("step", 64), ("step", 48)])
def test_selective_scan_kernels_compile_for_v5e_at_the_served_widths(one_chip, entry, rows):
    """``ops/pallas/selective_scan.py`` at Phi-4-mini-flash's widths (5 120
    channels, a state of 16): a chunk of one slot's rows (the chunk program's
    256, the whole-prompt program's 2 048, an odd count), and one row for each
    of 64 or 48 slots against a layer of the whole state pool, aliased: no
    copy of the pool is made."""
    from deepspeed_tpu.ops.pallas import selective_scan as ss

    d, N = 5120, 16

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    rowwise = (S((rows, d)), S((rows, d)), S((rows, N)), S((rows, N)), S((N, d)), S((d,)))
    if entry == "chunk":
        compiled = jax.jit(lambda *a: ss.scan_rows(*a, impl="pallas")).lower(*rowwise, S((N, d))).compile()
        assert len(re.findall(rf"^\s*(ROOT )?%?{ss.CHUNK_KERNEL}[.\d]* = .*custom-call\(", compiled.as_text(), re.M)) == 1
        return
    pool = S((9, rows, N, d))
    compiled = jax.jit(lambda *a: ss.scan_step(*a, 3, impl="pallas"), donate_argnums=(6,)).lower(*rowwise, pool).compile()
    assert len(re.findall(rf"^\s*(ROOT )?%?{ss.STEP_KERNEL}[.\d]* = .*custom-call\(", compiled.as_text(), re.M)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 9 * rows * N * d * 4 and mem.temp_size_in_bytes < 1e6   # the pool in place


@pytest.mark.parametrize("program", ["decode", "mixed", pytest.param("prefill", marks=pytest.mark.slow)])
def test_recurrent_family_programs_compile_at_the_served_size(one_chip, program, monkeypatch):
    """The ``phi4flash`` programs as its cell serves them (64 slots; 32
    sub-blocks of four kinds; 40 padded pair-heads on 10 kv pairs of 128
    lanes; ONE paged layer of 3 073 pages, 8 rings of 7 pages a slot, 9 scan
    states of [16, 5120] float32 a slot; a 2 048-token whole-prompt width; the
    whole 200 064-row vocabulary; 7.70 GB of bf16 weights as shapes): the
    paged kernels, token writes and scan kernels pass Mosaic, nothing re-lays
    a pool out, a decode step reads keys in 16 sub-blocks and advances 9
    states, and arguments + temps fit the chip."""
    import json

    from deepspeed_tpu.models import phi4flash
    from deepspeed_tpu.ops.pallas import selective_scan as ss
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    with open(os.path.join(root, "perfbench", "configs", "phi-4-mini-flash-serve-1chip.json")) as f:
        c = json.load(f)
    cfg = phi4flash.Phi4FlashConfig.from_dict(c)
    sv = c["serving"]
    B, page, P, Sp, C = (sv[k] for k in ("max_slots", "page_size", "num_pages", "max_prompt_len", "prefill_chunk_tokens"))
    W = -(-(Sp + sv["max_new_tokens"]) // page)
    ring = -(-(cfg.sliding_window + C) // page) + 1
    KV, D = cfg.n_kv_head, cfg.head_dim
    assert (B, W, ring, KV, D, P) == (64, 48, 7, 10, 128, B * W + 1)
    assert smodel.pool_layers(cfg.serving_family()) == (1, 8, 9)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: phi4flash.init_params(cfg, jax.random.PRNGKey(0))),
    )
    assert 7.69e9 < 2 * sum(x.size for x in jax.tree.leaves(params)) < 7.72e9      # 3.852B parameters
    pool, wpool = (
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16))
        for shape in ((1, P, KV, page, D), (8, 1 + B * ring, KV, page, D))
    )
    ssm, conv = (
        jax.ShapeDtypeStruct(shape, dt, sharding=_default_format(one_chip, shape, dt))
        for shape, dt in (((9, B, 16, 5120), jnp.float32), ((9, B, 3, 5120), jnp.bfloat16))
    )
    i32, u32 = jnp.int32, jnp.uint32
    fn, host = {
        "decode": (
            lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(
                cfg, p, tok, lens, c, bt, keys, ring=ring),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)),
        ),
        "mixed": (
            lambda p, c, tok, lens, bt, keys, ids, start, plen, pages, row, key, slot:
                smodel.paged_mixed_step(
                    cfg, p, tok, lens, ids, start, plen, c, bt, pages, row, keys, key,
                    slot=slot, ring=ring),
            (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32),
             sds((1, C), i32), sds((), i32), sds((), i32), sds((C // page,), i32),
             sds((1, W), i32), sds((2,), u32), sds((), i32)),
        ),
        "prefill": (
            lambda p, c, ids, plen, pages, key, slot: smodel.paged_prefill(
                cfg, p, ids, plen, c, pages, key, slot=slot, ring=ring),
            (sds((1, Sp), i32), sds((), i32), sds((Sp // page,), i32), sds((2,), u32), sds((), i32)),
        ),
    }[program]
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params,
        cache=Cache(pool, pool, None, wpool, wpool, ssm, conv),
        _kv_axis=2, num_pages=P, page_size=page, n_kv_head=KV, head_dim=D, n_layer=1,
    )
    compiled = pset.aot(fn, host, with_params=True)
    text = compiled.as_text()
    assert pset.program_census(program, compiled)[0] == 0  # or it raises

    def kernels(name):
        return len(re.findall(rf"^\s*%?{name}[.\d]* = .*custom-call\(", text, re.M))

    # the one-token kernel: 8 rings, the paged layer and its 7 cross readers (in the decode program it takes the
    # enclosing function's name, the scheduler's ``decode_fn``; here a lambda's); a token write where K/V are made
    want = {"decode": {"kv_token_write": 9, ss.STEP_KERNEL: 9},
            # the chunk rows' multi-token kernel on the 9 attentions; behind the stop the chunk's sampled row
            # rides the cross layers' one-token call
            "mixed": {"decode_fn": 16, "kv_token_write": 9, "chunk_fn": 9, ss.STEP_KERNEL: 9, ss.CHUNK_KERNEL: 9},
            # the whole prompt: blocked jnp attention, the last row's 7 cross reads through the one-token kernel
            "prefill": {ss.CHUNK_KERNEL: 9}}[program]
    got = {name: kernels(name) for name in want}
    print(program, got, text.count('custom_call_target="tpu_custom_call"'))
    assert got == want
    assert text.count('custom_call_target="tpu_custom_call"') == {"decode": 16 + 9 + 9, "mixed": 16 + 9 + 9 + 9 + 9, "prefill": 9 + 7}[program]
    mem = compiled.memory_analysis()
    print(program, "argument", mem.argument_size_in_bytes, "temp", mem.temp_size_in_bytes)
    # 7.70 GB of weights, 2.01 GB of paged pool, 2.35 GB of rings, 0.21 GB of recurrent state
    assert 12.2e9 < mem.argument_size_in_bytes < 12.4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9   # of the chip's 16


# -- the gated delta rule (qwen3_next): the two kernels at the published heads (ISSUE 52) --

@pytest.mark.parametrize("entry,rows", [("chunk", 256), ("chunk", 37), ("step", 128)])
def test_gated_delta_kernels_compile_for_v5e_at_the_published_heads(one_chip, entry, rows):
    """``ops/pallas/gated_delta.py`` at Qwen3-Next's heads (16 key heads and 32
    value heads of 128 x 128): a chunk of one slot's rows (the chunk program's
    256, an odd count), and one row for each of 128 slots against a layer of
    the whole 2.4 GB state pool, aliased: no copy of the pool is made."""
    from deepspeed_tpu.ops.pallas import gated_delta as gd

    Hk, Hv, dk, dv = 16, 32, 128, 128

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rowwise = (S((rows, Hk, dk)), S((rows, Hk, dk)), S((rows, Hv, dv)), S((rows, Hv)), S((rows, Hv)))
    if entry == "chunk":
        compiled = jax.jit(lambda *a: gd.chunk_rows(*a, impl="pallas")).lower(*rowwise, S((Hv, dk, dv))).compile()
        assert len(re.findall(rf"^\s*(ROOT )?%?{gd.CHUNK_KERNEL}[.\d]* = .*custom-call\(", compiled.as_text(), re.M)) == 1
        return
    pool = S((9, rows, Hv, dk, dv))
    compiled = jax.jit(lambda *a: gd.step(*a[:6], 3, a[6], impl="pallas"), donate_argnums=(5,)).lower(
        *rowwise, pool, S((rows,), jnp.bool_)).compile()
    assert len(re.findall(rf"^\s*(ROOT )?%?{gd.STEP_KERNEL}[.\d]* = .*custom-call\(", compiled.as_text(), re.M)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 9 * rows * Hv * dk * dv * 4 and mem.temp_size_in_bytes < 16e6   # the pool in place


# -- the delta rule with a decay a key channel (ling3, KDA): the two kernels at the published heads (ISSUE 60) --

@pytest.mark.parametrize("entry,rows", [("chunk", 256), ("step", 128)])
def test_kda_kernels_compile_for_v5e_at_the_published_heads(one_chip, entry, rows):
    """``ops/pallas/gated_delta.py`` under a decay a KEY CHANNEL at Ling-3.0-flash's
    heads (32 of 128 x 128, a key head a value head, ``g [rows, 32, 128]``): a
    chunk of one slot's rows (sub-chunks of 64 in diagonal blocks of 16), and
    one row for each of 128 slots against a layer of the whole 2.7 GB state
    pool, aliased: no copy of the pool is made. Under their own names: the
    scalar rule's kernels are in neither program."""
    from deepspeed_tpu.ops.pallas import gated_delta as gd

    H, dk, dv = 32, 128, 128

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    count = lambda name, text: len(re.findall(rf"^\s*(ROOT )?%?{name}[.\d]* = .*custom-call\(", text, re.M))  # noqa: E731
    rowwise = (S((rows, H, dk)), S((rows, H, dk)), S((rows, H, dv)), S((rows, H, dk)), S((rows, H)))
    if entry == "chunk":
        text = jax.jit(lambda *a: gd.chunk_rows(*a, impl="pallas", g_min=-5.0)).lower(*rowwise, S((H, dk, dv))).compile().as_text()
        assert count(gd.KDA_CHUNK_KERNEL, text) == 1 and count(gd.CHUNK_KERNEL, text) == 0
        return
    pool = S((10, rows, H, dk, dv))
    compiled = jax.jit(lambda *a: gd.step(*a[:6], 3, a[6], impl="pallas"), donate_argnums=(5,)).lower(
        *rowwise, pool, S((rows,), jnp.bool_)).compile()
    assert count(gd.KDA_STEP_KERNEL, compiled.as_text()) == 1 and count(gd.STEP_KERNEL, compiled.as_text()) == 0
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 10 * rows * H * dk * dv * 4 and mem.temp_size_in_bytes < 16e6   # the pool in place


# -- a multi-stream residual (xing4_0): the mixing's kernel pair at the published widths (ISSUE 57) --

def _x4_config():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    with open(os.path.join(root, "perfbench", "configs", "xing4.0-29b-a4b-ep8-l20-serve-1chip.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("rows", [64, 320, 150], ids=["decode", "mixed", "a-partial-block"])
def test_hyper_connection_kernels_compile_for_v5e_at_the_published_widths(one_chip, rows):
    """``ops/pallas/hyper_connection.py`` at Xing4.0's four streams of 3 584:
    the decode step's 64 rows, the mixed step's 320 and a count the grid has to
    pad; ``hc_post`` writes the stream in place."""
    from deepspeed_tpu.ops.pallas import hyper_connection as hc

    n, E = 4, 3584
    K = 2 * n + n * n

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pre = hc._pre.lower(S((rows, n * E)), S((K, n * E)), S((3,)), S((K,)), n=n, eps=1e-6, iters=20,
                        clamp=(-30.0, 30.0), impl="pallas").compile()
    assert len(re.findall(rf"^\s*(ROOT )?%?{hc.PRE_KERNEL}[.\d]* = .*custom-call\(", pre.as_text(), re.M)) == 1
    post = jax.jit(lambda x, y, m: hc._post(x, y, m, n=n, impl="pallas"), donate_argnums=(0,)).lower(
        S((rows, n * E)), S((rows, E)), S((rows, K), jnp.float32)).compile()
    assert len(re.findall(rf"^\s*(ROOT )?%?{hc.POST_KERNEL}[.\d]* = .*custom-call\(", post.as_text(), re.M)) == 1
    mem = post.memory_analysis()
    assert mem.alias_size_in_bytes >= rows * n * E * 2 and mem.temp_size_in_bytes < 1e6       # the stream in place (rows padded to whole tiles)


def test_multi_stream_family_decode_program_compiles_with_the_stream_kept_on_the_chip(one_chip, monkeypatch):
    """The ``xing4_0`` decode program as the cell serves it but FOUR layers deep
    (both dense layers and two expert layers; 64 slots, 32 heads on a 640-lane
    latent row, 8 held experts of 64): two mixing kernels a sub-block under the
    names the readers find them by, nothing re-lays the pool out, and the
    compiler keeps the stream in the chip's fast memory between them (``S(1)``
    on the kernels' results: what ``kernel_costs_xing4.hc_mix`` counts no HBM
    byte of a row for)."""
    from deepspeed_tpu.models import xing4
    from deepspeed_tpu.ops.pallas import hyper_connection as hc
    from deepspeed_tpu.serving import model as smodel
    from deepspeed_tpu.serving.placement import Placement, ProgramSet

    c = dict(_x4_config(), num_hidden_layers=4)
    cfg = xing4.Xing4Config.from_dict(c)
    sv = c["serving"]
    B, page, P = sv["max_slots"], sv["page_size"], sv["num_pages"]
    W = -(-(sv["max_prompt_len"] + sv["max_new_tokens"]) // page)
    L = cfg.n_layer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.PRNGKey(0))),
    )
    shape = (L, P, 1, page, 640)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_default_format(one_chip, shape, jnp.bfloat16))
    i32, u32 = jnp.int32, jnp.uint32
    pset = object.__new__(ProgramSet)
    pset.__dict__.update(
        placement=Placement("v5e", [one_chip._device], 1), params=params, cache=Cache(pool),
        _kv_axis=2, num_pages=P, page_size=page, n_kv_head=1, head_dim=640, n_layer=L,
    )
    compiled = pset.aot(
        lambda p, c, tok, lens, bt, keys: smodel.paged_decode_step(cfg, p, tok, lens, c, bt, keys),
        (sds((B,), i32), sds((B,), i32), sds((B, W), i32), sds((B, 2), u32)), with_params=True,
    )
    text = compiled.as_text()
    assert pset.program_census("decode", compiled)[0] == 0  # or it raises
    for kernel in (hc.PRE_KERNEL, hc.POST_KERNEL):
        lines = re.findall(rf"^\s*%?{kernel}[.\d]* = (.*?) custom-call\(", text, re.M)
        assert len(lines) == 2 * L and all("S(1)" in x for x in lines), kernel
    # an attention kernel and a token write a layer, four mixing kernels, the grouped kernel an expert layer
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2 * L + 4 * L + (L - 2)
    assert "dspart.hc.mix/hc_pre" in text and "dspart.hc.mix/hc_post" in text
