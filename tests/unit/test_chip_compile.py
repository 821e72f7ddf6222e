"""Kernels of the serving path compiled for the v5e at the benchmark's
widths, without a chip: the TPU's compiler is installed here and compiles
for a described topology. What Mosaic refuses (a slice off the tiling, too
much VMEM, an unsupported relayout) interpret mode never shows; this does,
at no chip time. Nothing runs, so it says nothing about results or speed.

Keep every such compile in THIS file: the topology is described inside a
fixture, so only the pytest worker that is handed this file loads libtpu.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (rows of the tick bucket, query heads, KV heads, blocks of a layer's pool,
# table tier) of the two serving cells, depth 16, blocks of 32, heads of 128
PAGED_SHAPES = {
    "mistral7b-512x64": (512, 32, 8, 2400, 64),
    "mistral7b-64x16": (64, 32, 8, 2400, 16),
    "pythia69b-512x24": (512, 32, 32, 640, 24),
    "pythia69b-64x24": (64, 32, 32, 640, 24),
    # the looped cell: 192 cache layers (4 passes x 48 layers) of 193 blocks
    # = 16 x 2,316; 16 query = 16 KV heads
    "ouro26b-512x16": (512, 16, 16, 2316, 16),
    "ouro26b-64x16": (64, 16, 16, 2316, 16),
}


@pytest.mark.parametrize("shape", sorted(PAGED_SHAPES))
def test_paged_attention_compiles_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    T, N, K, NB, MB = PAGED_SHAPES[shape]

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = arg((16 * NB, 32, K, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, n: paged_attention(q, k, v, t, n, interpret=False)
    ).lower(arg((T, N, 128), jnp.bfloat16), pool, pool,
            arg((T, MB), jnp.int32), arg((T,), jnp.int32)).compile()
    text = compiled.as_text()
    # one Mosaic call, operands (tables, lengths+same, q, kpool, vpool):
    # benchmarks/roofline/paged_attention.py reads the pool at operand 3
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1


# (rows of the tick bucket, table tier) of the latent serving cell: 16 query
# heads on one latent row of 512 + 64 values stored 640 wide, depth 9,
# 4,352 blocks of 32 a layer
LATENT_SHAPES = {"moonlight16b-512x256": (512, 256),
                 "moonlight16b-64x64": (64, 64)}


@pytest.mark.parametrize("shape", sorted(LATENT_SHAPES))
def test_latent_paged_attention_compiles_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import \
        latent_paged_attention

    T, MB = LATENT_SHAPES[shape]

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, pool, t, n: latent_paged_attention(
            q, pool, t, n, 512, 192 ** -0.5, interpret=False)
    ).lower(arg((T, 16, 640), jnp.bfloat16),
            arg((9 * 4352, 32, 640), jnp.bfloat16),
            arg((T, MB), jnp.int32), arg((T,), jnp.int32)).compile()
    text = compiled.as_text()
    # one Mosaic call under its own name, operands (tables, lengths+same,
    # q, pool): benchmarks/roofline/latent_paged_attention.py classifies
    # by the name and reads the pool at operand 3
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%latent_paged_attention" in text


# (rows of the tick bucket, table tier, window) of the stack-of-kinds serving
# cell: differential attention's 40 paired query heads of 128 on 10 paired
# KV heads, blocks [10, 32, 128] heads first (10 is off the sublane tiling:
# as a block's second-minor dim Mosaic refuses the copy's slice), a ring of
# 73 slots x 32 blocks x 8 layers or the one layer's 10,900 blocks
KINDS_SHAPES = {
    "phi4flash-window-512x136": (512, 136, 512, 8 * 73 * 32),
    "phi4flash-window-64x34": (64, 34, 512, 8 * 73 * 32),
    "phi4flash-shared-512x136": (512, 136, None, 10900),
    "phi4flash-shared-64x34": (64, 34, None, 10900),
}


@pytest.mark.parametrize("shape", sorted(KINDS_SHAPES))
def test_paired_head_attention_compiles_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    T, MB, window, rows = KINDS_SHAPES[shape]
    name = "shared_paged_attention" if window is None \
        else "window_paged_attention"

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = arg((rows, 10, 32, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, n: paged_attention(
            q, k, v, t, n, interpret=False, scale=0.125, window=window,
            heads_first=True, name=name)
    ).lower(arg((T, 40, 128), jnp.bfloat16), pool, pool,
            arg((T, MB), jnp.int32), arg((T,), jnp.int32)).compile()
    text = compiled.as_text()
    # one Mosaic call under its own name, operands (tables, lengths+same,
    # q, kpool, vpool): benchmarks/roofline/{window,shared}_paged_attention
    # classify by the name and read the pool at operand 3
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert f"%{name}" in text


# (rows of the tick bucket, table tier, window, rows of the pool) of the
# cell of window and full layers over experts (the ``afmoe`` family): 48
# query heads on 8 KV heads of 128 (a group of 6: a decode row's queries are
# 6 rows of the head-major scratch from a run-time offset, which a 16-bit
# scratch refuses), bfloat16 products, ONE table a sequence slot (a table a
# row, 2,048 x 384 words, does not fit scalar memory), rings of 28 + 1
# slots x 192 blocks x 4 layers or the one full layer's 12,288 blocks
SPAN_SHAPES = {
    "trinity-swa-2048x360": (2048, 360, 4096, 4 * 29 * 192),
    "trinity-swa-256x90": (256, 90, 4096, 4 * 29 * 192),
    "trinity-global-2048x360": (2048, 360, None, 12288),
    "trinity-global-256x180": (256, 180, None, 12288),
}


@pytest.mark.parametrize("shape", sorted(SPAN_SHAPES))
def test_window_and_full_attention_compile_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    T, MB, window, rows = SPAN_SHAPES[shape]
    name = "global_attention" if window is None else "swa_attention"

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = arg((rows, 32, 8, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, n, w: paged_attention(
            q, k, v, t, n, interpret=False, window=window, name=name,
            mxu_dtype=jnp.bfloat16, row_table=w)
    ).lower(arg((T, 48, 128), jnp.bfloat16), pool, pool,
            arg((29, MB), jnp.int32), arg((T,), jnp.int32),
            arg((T,), jnp.int32)).compile()
    text = compiled.as_text()
    # one Mosaic call under its own name, operands (tables, lengths + same
    # + slots, q, kpool, vpool): benchmarks/roofline/{swa,global}_attention
    # classify by the name and read the pool at operand 3
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert f"%{name}" in text


# (rows of the tick bucket) of the ``lfm2_moe`` serving cell: 32 query heads
# on 8 KV heads of 64 stored two to a pool row (``paged.kv_lane_pack``:
# ``[bs, 4, 128]``; a block ``[bs, 8, 64]`` Mosaic refuses at the strided
# load of a slot whose last dim is not 128), 272 + 1 sequence slots with a
# table of 64 blocks each, two attention layers x 20,480 blocks
PACKED_SHAPES = {"lfm2-global-2048x64": 2048, "lfm2-global-256x64": 256}


@pytest.mark.parametrize("shape", sorted(PACKED_SHAPES))
def test_lane_packed_attention_compiles_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    T = PACKED_SHAPES[shape]

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def lowered(pool_dims, head):
        pool = arg(pool_dims, jnp.bfloat16)
        return jax.jit(
            lambda q, k, v, t, n, w: paged_attention(
                q, k, v, t, n, interpret=False, name="global_attention",
                scale=0.125, mxu_dtype=jnp.bfloat16, row_table=w)
        ).lower(arg((T, 32, head), jnp.bfloat16), pool, pool,
                arg((273, 64), jnp.int32), arg((T,), jnp.int32),
                arg((T,), jnp.int32))

    text = lowered((40960, 32, 4, 128), 128).compile().as_text()
    # one Mosaic call under the full layers' name, the pool at operand 3
    # as ``benchmarks/roofline/swa_attention.position_bytes`` reads it
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%global_attention" in text
    if T == 256:
        with pytest.raises(Exception, match="last dim size is not 128"):
            lowered((40960, 32, 8, 64), 64).compile()


# (rows of the tick bucket) of the ``kimi_linear`` serving cell: 32 latent
# heads on rows of 512 + 64 values stored 640 wide, two latent layers x
# 40,960 blocks of 32, ONE table a sequence slot (272 + 1 of 128 blocks: a
# table a row of a 2,048-row tick is a megabyte of scalar memory, which the
# whole tick compiled here refused, PERF.md, PR 41); and the delta rule's
# one-row form over six layers' 273 matrices of 32 heads of 128 x 128 in
# float32, updated in place
KIMI_SHAPES = {"kimi-linear-2048x128": 2048, "kimi-linear-256x128": 256}


@pytest.mark.parametrize("shape", sorted(KIMI_SHAPES))
def test_latent_attention_by_slot_compiles_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.paged_attention import \
        latent_paged_attention

    T = KIMI_SHAPES[shape]

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def lowered(tables, by_slot):
        return jax.jit(
            lambda q, pool, t, n, w: latent_paged_attention(
                q, pool, t, n, 512, 192 ** -0.5, interpret=False,
                row_table=w if by_slot else None)
        ).lower(arg((T, 32, 640), jnp.bfloat16),
                arg((2 * 40960, 32, 640), jnp.bfloat16),
                arg(tables, jnp.int32), arg((T,), jnp.int32),
                arg((T,), jnp.int32))

    text = lowered((273, 128), True).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%latent_paged_attention" in text
    if T == 2048:
        with pytest.raises(Exception, match="smem"):
            lowered((T, 128), False).compile()


def test_kda_step_compiles_for_v5e(one_chip):
    from deepspeed_tpu.ops.pallas.kda import kda_step

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    row = arg((256, 32, 128), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, a, b, state, slots, fresh: kda_step(
            q, k, v, a, b, state, slots, fresh, interpret=False),
        donate_argnums=(5,)
    ).lower(row, row, row, row, arg((256, 32), jnp.float32),
            arg((6 * 273, 32, 128, 128), jnp.float32),
            arg((256,), jnp.int32), arg((256,), jnp.bool_)).compile()
    text = compiled.as_text()
    # one Mosaic call under its own name (``benchmarks/roofline/kda_step.py``
    # classifies by it), the store of states aliased in and out: no copy of
    # its 3.4 GB, nothing beside the rows' vectors held
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%kda_step" in text
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 6 * 273 * 32 * 128 * 128 * 4
    assert stats.temp_size_in_bytes < 64 << 20


def test_ssd_step_compiles_for_v5e(one_chip):
    """The one-row form of the Mamba-2 recurrence at the ``nemotron_h``
    cell's size: 128 heads of 64 x 128 in 8 groups, five layers x (136 + 1)
    slots' matrices stored ``[64, 128, 128]`` a slot (the state values down
    a tile's rows, two heads' channels along its lanes)."""
    from deepspeed_tpu.ops.pallas.ssd import ssd_step, store_shape

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert store_shape(128, 8, 64, 128) == (64, 128, 128)
    compiled = jax.jit(
        lambda x, d, a, B, C, state, slots, fresh: ssd_step(
            x, d, a, B, C, state, slots, fresh, interpret=False),
        donate_argnums=(5,)
    ).lower(arg((256, 128, 64), jnp.float32), arg((256, 128), jnp.float32),
            arg((256, 128), jnp.float32), arg((256, 8, 128), jnp.float32),
            arg((256, 8, 128), jnp.float32),
            arg((5 * 137, 64, 128, 128), jnp.float32),
            arg((256,), jnp.int32), arg((256,), jnp.bool_)).compile()
    text = compiled.as_text()
    # one Mosaic call under its own name (``benchmarks/roofline/ssd_step.py``
    # classifies by it), the store of states aliased in and out: no copy of
    # its 2.9 GB, nothing beside the rows' vectors held
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%ssd_step" in text
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 5 * 137 * 64 * 128 * 128 * 4
    assert stats.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [2048, 128])
def test_ssd_chunk_compiles_for_v5e(one_chip, rows):
    """The chunked form (plain XLA today) at the cell's chunk bucket and at
    one chunk: no Mosaic call, the store carried through the pieces' loop
    in place, and nothing as large as the store made beside it."""
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.ops.pallas.ssd import ssd_chunk

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def form(x, d, g, B, C, state, slot, positions):
        return ssd_chunk(x, d, g, B, C, HY.runs_of(slot, positions),
                         slot > 0, state, slot, 128)

    store = 5 * 137 * 64 * 128 * 128 * 4
    compiled = jax.jit(form, donate_argnums=(5,)).lower(
        arg((rows, 128, 64), jnp.float32), arg((rows, 128), jnp.float32),
        arg((rows, 128), jnp.float32), arg((rows, 8, 128), jnp.float32),
        arg((rows, 8, 128), jnp.float32),
        arg((5 * 137, 64, 128, 128), jnp.float32),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= store
    # the sums within the chunks: [rows / 128, 128 heads, 128, 128] float32
    # three times over, and the rows' own arrays
    assert stats.temp_size_in_bytes < (1 << 30 if rows == 2048 else 128 << 20)


@pytest.mark.parametrize("rows", [2048, 256])
def test_attention_over_a_two_head_pool_compiles_for_v5e(one_chip, rows):
    """The ``nemotron_h`` cell's one attention layer: 32 query heads on TWO
    key-value heads of 128, a block heads first, ``[2, 32, 128]`` (a block
    ``[32, 2, 128]`` has a second-minor dimension of 2, which a tile pads
    eight times over: ``paged.cache_kinds``), 12,288 blocks, one table of
    80 blocks a sequence slot."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = arg((12288, 2, 32, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, n, w: paged_attention(
            q, k, v, t, n, interpret=False, name="global_attention",
            heads_first=True, mxu_dtype=jnp.bfloat16, row_table=w)
    ).lower(arg((rows, 32, 128), jnp.bfloat16), pool, pool,
            arg((137, 80), jnp.int32), arg((rows,), jnp.int32),
            arg((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%global_attention" in text
    # the pool as it lies, unpadded: two pools of 12,288 x 2 x 32 x 128 x 2 B
    stats = compiled.memory_analysis()
    assert stats.argument_size_in_bytes < 2 * 12288 * 2 * 32 * 128 * 2 * 1.05


@pytest.mark.parametrize("rows", [2048, 256])
def test_kda_chunk_compiles_for_v5e(one_chip, rows):
    """The chunk form at the Kimi cell's two buckets: one Mosaic call under
    its own name inside the tick's ``cond`` (``benchmarks/roofline/
    kda_chunk.py`` finds it by the scope), a grid as long as the tick's
    pieces, the store aliased through the ``cond`` and the call."""
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.ops.pallas.kda import kda_chunk

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def form(q, k, v, g, b, state, slot, positions):
        return kda_chunk(q, k, v, g, b, HY.runs_of(slot, positions),
                         slot > 0, state, slot, interpret=False)

    row = arg((rows, 32, 128), jnp.float32)
    compiled = jax.jit(form, donate_argnums=(5,)).lower(
        row, row, row, row, arg((rows, 32), jnp.float32),
        arg((6 * 273, 32, 128, 128), jnp.float32),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%kda_chunk" in text
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 6 * 273 * 32 * 128 * 128 * 4
    # beside the result [rows, 32, 128]: the pieces' scalars and masks
    assert stats.temp_size_in_bytes < 4 << 20


# (sequences a chip, their length, query heads, KV heads, head size, dtype,
# blocks a caller names): the two training cells at the blocks
# ``choose_blocks`` gives them, and what only Mosaic refuses: a length under a
# lane row that is no power of two (a q block of 64 in arrays padded to 128),
# a named block of 64, and float32 operands and heads of 256 at whole blocks
FLASH_SHAPES = {
    "mistral7b-1x4096": (1, 4096, 32, 8, 128, jnp.bfloat16, {}),
    "pythia69b-2x2048": (2, 2048, 32, 32, 128, jnp.bfloat16, {}),
    "short-1x100": (1, 100, 4, 2, 128, jnp.bfloat16, {}),
    "named-64x128": (1, 512, 4, 4, 128, jnp.bfloat16,
                     dict(block_q=64, block_kv=128)),
    "float32-1x2048": (1, 2048, 4, 4, 128, jnp.float32, {}),
    "float32-256-1x2048": (1, 2048, 4, 2, 256, jnp.float32, {}),
}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_kernels_compile_for_v5e(one_chip, shape, monkeypatch):
    import importlib

    from benchmarks.roofline import flash_attention as need
    from benchmarks.trace_reduce import Op

    # the package exports the function under the module's own name
    F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(F, "_use_interpret", lambda: False)
    B, S, N, K, D, dtype, blocks = FLASH_SHAPES[shape]
    block_q = min(blocks.get("block_q") or F.choose_blocks(S, S)[0],
                  F._round_pow2(S))
    S_pad = -(-S // block_q) * block_q

    def arg(heads):
        return jax.ShapeDtypeStruct((B, S, heads, D), dtype,
                                    sharding=one_chip)

    def grads(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, **blocks), q, k, v)
        return (o,) + back(do)

    text = jax.jit(grads).lower(arg(N), arg(K), arg(K), arg(N)) \
        .compile().as_text()
    # a trace names an event by the instruction with its operands' types;
    # the compiled text keeps those under ``operand_layout_constraints``
    calls = [Op("", "custom-call", re.sub(
        r"custom-call\(.*?\), (.*operand_layout_constraints=\{(.+?\})\}, )",
        r"custom-call(\2), \1", line), 0.0, 0.0)
        for line in text.splitlines()
        if "custom_call_target=\"tpu_custom_call\"" in line]
    # the benchmark's reader tells the three apart by operand and result
    # counts and reads B*N, S, D off operand 0: what it finds here is what
    # ``flash_attention_roofline`` is reckoned from
    assert sorted(need.classify(c) for c in calls) == ["dkv", "dq", "fwd"]
    for c in calls:
        matmuls = need._MATMULS[need.classify(c)]
        assert need.ops_and_bytes(need.classify(c), c.text)[0] == \
            matmuls * B * N * S_pad * S_pad * D


def _mosaic_calls(text):
    """The Mosaic calls of a compiled program as a trace would name them:
    the instruction's name, and its text with the operands' types (which
    the compiled text keeps under ``operand_layout_constraints``)."""
    from benchmarks.trace_reduce import Op

    calls = []
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in line:
            continue
        name = line.split("=", 1)[0].strip().lstrip("%")
        calls.append(Op(name, "custom-call", re.sub(
            r"custom-call\(.*?\), (.*operand_layout_constraints=\{(.+?\})\}, )",
            r"custom-call(\2), \1", line), 0.0, 0.0))
    return calls


# the flash kernels under a WINDOW at the training cell that has one: a
# chip's share of a step (2 x 8,192, 32 query / 4 KV heads of 128, window
# 1,024) and, so that the geometry is not the one case, a window longer than
# a block at Mistral's 1 x 4,096 x 32 / 8
WINDOW_FLASH_SHAPES = {
    "mellum2-2x8192-w1024": (2, 8192, 32, 4, 128, 1024),
    "gqa-1x4096-w1536": (1, 4096, 32, 8, 128, 1536),
}


@pytest.mark.parametrize("shape", sorted(WINDOW_FLASH_SHAPES))
def test_window_flash_kernels_compile_for_v5e(one_chip, shape, monkeypatch):
    import importlib

    from benchmarks.roofline import flash_attention as full
    from benchmarks.roofline import window_flash_attention as need

    F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(F, "_use_interpret", lambda: False)
    B, S, N, K, D, window = WINDOW_FLASH_SHAPES[shape]

    def arg(heads):
        return jax.ShapeDtypeStruct((B, S, heads, D), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return (o,) + back(do)

    text = jax.jit(grads).lower(arg(N), arg(K), arg(K), arg(N)) \
        .compile().as_text()
    calls = _mosaic_calls(text)
    # three Mosaic calls under names of their own: the window's reader finds
    # them by name, with the live area for their need
    assert sorted(need.classify(c) for c in calls) == ["dkv", "dq", "fwd"]
    assert all("window_flash_" in c.name for c in calls)
    area = S * window - window * window / 2
    for c in calls:
        kind = need.classify(c)
        assert need.ops_and_bytes(kind, c.text, window)[0] == \
            need._MATMULS[kind] * 2 * B * N * area * D
        # the full kernels' reader would take it for a causal square
        assert full.ops_and_bytes(kind, c.text)[0] > \
            3.9 * need.ops_and_bytes(kind, c.text, window)[0] * (
                1 if window == 1024 else 0.4)


# the grouped matmuls of the training cell with experts, forward and
# backward, at a layer's shapes: a row a pair of the step's 2 x 8,192 x 8 x 2,304
# against 16 held experts' [2,304, 896] and [896, 2,304], each product under
# the tiles of its own shapes
def test_training_gmm_and_tgmm_compile_for_v5e(one_chip, monkeypatch):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    from benchmarks.roofline import train_expert_gmm as need
    from deepspeed_tpu.moe import layer as MOE

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = 2 * 8192 * 8
    bf = jnp.bfloat16
    traced = []
    for name in ("gmm", "tgmm"):
        def spy(*a, _name=name, _real=getattr(backend, name), **kw):
            # (product, the matrices' [K, N] as the forward has them, tiles)
            kn = a[1].shape[1:] if _name == "gmm" else \
                (a[0].shape[0], a[1].shape[1])
            traced.append((_name + "T" * kw.get("transpose_rhs", False),
                           tuple(kn), a[4]))
            return _real(*a, **kw)
        monkeypatch.setattr(backend, name, spy)

    def arg(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def ffn(x, experts, sizes):
        return jnp.sum(MOE.ragged_expert_ffn(
            x, sizes, experts, "swiglu").astype(jnp.float32))

    experts = {"w_up": arg((16, 2304, 896)), "w_gate": arg((16, 2304, 896)),
               "w_down": arg((16, 896, 2304))}
    # Mosaic refuses a call whose scoped VMEM is over the limit (16 MiB):
    # that this compiles says every product's is under it
    text = jax.jit(jax.grad(ffn, (0, 1))).lower(
        arg((rows, 2304)), experts, arg((16,), jnp.int32)).compile().as_text()
    calls = _mosaic_calls(text)
    kinds = [need.classify(c) for c in calls]
    # forward three, the rows' gradient three (XLA may share one of them:
    # the sum's cotangent is a constant), the matrices' three
    assert kinds.count("gmm") in (5, 6) and kinds.count("tgmm") == 3
    pairs = 2 * 8192 * 8 // 4
    for c, kind in zip(calls, kinds):
        ops, moved = need.ops_and_bytes(kind, c.text, pairs)
        assert ops == 2.0 * pairs * 2304 * 896, (kind, c.text[:200])
        assert moved >= 2 * (16 * 2304 * 896 + pairs * 896)
        # the rows' tile, read off the call: its scalar-prefetched table of
        # visits has a row tile's entry and one more a group but the first
        visits = [int(n) for n in re.findall(r"s32\[(\d+)\]", c.text)]
        assert rows // 256 + 16 - 1 in visits, c.text[:300]
    # the three products of ONE matrix run under three triples of their
    # own, none with a remainder, each what the rule gives for its shapes
    for kn in ((2304, 896), (896, 2304)):
        fwd, dgrad, wgrad = MOE.gmm_tilings(rows, *kn, 16, 2)
        assert len({fwd, dgrad, wgrad}) == 3
        got = {p: t for p, shape, t in traced if shape == kn}
        assert got == {"gmm": fwd, "gmmT": dgrad, "tgmm": wgrad}
        (K, N) = kn
        for (tm, tk, tn), (k, n) in zip((fwd, dgrad, wgrad),
                                        ((K, N), (N, K), (K, N))):
            assert rows % tm == 0 and k % tk == 0 and n % tn == 0


def test_a_shares_movers_compile_for_v5e_and_copy_no_sorted_array(
        one_chip, monkeypatch):
    """A share of an expert layer at the training cell's shape, forward
    and backward: the movers' loops and the calls that hand them their
    arrays compile, no ``[131072, .]`` array is copied around them (an
    array two loops shared was: PR 50), and the layer's temporaries are
    the step's (4.3 GB here, 4.47 in the step; the plain forms' step 4.02)."""
    from benchmarks.roofline import train_expert_gmm as need
    from deepspeed_tpu.moe import layer as MOE

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, k, width, inter, held = 2 * 8192, 8, 2304, 896, 16
    bf = jnp.bfloat16

    def arg(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def share(x, w, idx, experts):
        y, _ = MOE._held_routed(x, w, idx, experts, "swiglu", 0, 64)
        return jnp.sum(y.astype(jnp.float32))

    experts = {"w_up": arg((held, width, inter)),
               "w_gate": arg((held, width, inter)),
               "w_down": arg((held, inter, width))}
    operands = (arg((rows, width)), arg((rows, k)),
                arg((rows, k), jnp.int32), experts)

    assert MOE.held_tiles(rows * k, rows) == (512, 512)
    live = jax.jit(jax.grad(share, (0, 1, 3))).lower(*operands).compile()
    text = live.as_text()
    assert " while(" in text
    assert not [s for s in re.findall(r"= (\S+) copy\(", text)
                if s.startswith(f"bf16[{rows * k},")]
    # the roofline of the grouped matmuls takes the calls it took: the
    # nine of the forward, the rows' gradient and the matrices', none new
    kinds = [need.classify(c) for c in _mosaic_calls(text)]
    assert kinds.count(None) >= 5
    assert kinds.count("gmm") in (5, 6) and kinds.count("tgmm") == 3
    # the arrays the loops fill are made when a loop can start and share
    # memory like any other (as ``lax.empty`` they did not: 17.8 GB a step)
    assert live.memory_analysis().temp_size_in_bytes < 4.6e9


# (cell, rows of its small tick bucket, its widest table tier, the
# convolution-state store): the decode programs of the three cells whose
# sequences keep a convolution's last inputs, whole, at the cells' real
# sizes. A store whose second-minor dimension was its 2 or 3 stored inputs
# padded every tile, and XLA re-laid all of it on entry, on exit and (the
# 121 MB ``kda_conv``) as four ``remat_compressed`` pairs a tick (PERF.md,
# PR 43); a row an input of a slot is re-laid nowhere
TICK_PROGRAMS = {
    "kimi-linear-256x80": ("serve-kimi-linear-48b-rollout-closed", 256, 80,
                           "kda_conv"),
    "lfm2-256x64": ("serve-lfm2-24b-concurrent-closed", 256, 64, "conv"),
    "phi4flash-64x136": ("serve-phi4flash-reason-closed", 64, 136, "conv"),
}


def _copies_tool():
    """``tools/tick_program_copies.py``: the one place that compiles a
    cell's tick and counts its copies."""
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "tick_program_copies.py")
    spec = importlib.util.spec_from_file_location("tick_program_copies", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# (cell, rows of a tick bucket, its table tier): both tick programs of the
# cell of sparse layers, whole, at its real sizes: keys, values and index
# keys ride the tick in place, and neither the walks nor the scatter at
# (block, offset) copies a store (a layer's share of any of the three is
# over 100 MB)
SPARSE_TICKS = {
    "keye-vl2-256x144": ("serve-keye-vl2-30b-longctx-closed", 256, 144),
    "keye-vl2-2048x144": ("serve-keye-vl2-30b-longctx-closed", 2048, 144),
    # the most rows against the narrowest tables: the most tables a tick
    # takes into scalar memory (``paged.tick_tables``; a table a row of
    # this program does not fit there)
    "keye-vl2-2048x36": ("serve-keye-vl2-30b-longctx-closed", 2048, 36),
}


@pytest.mark.parametrize("program", sorted(SPARSE_TICKS))
def test_a_tick_of_sparse_layers_copies_no_store(one_chip, program):
    import math

    tool = _copies_tool()
    cell, rows, tier = SPARSE_TICKS[program]
    cfg, sizes, programs = tool.cell_programs(cell)
    assert (rows, tier) in programs
    lowered, pool = tool.lower_tick(cfg, sizes, rows, tier, one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    stores = [math.prod(pool[name].shape) for name in ("k", "v", "idx")]
    found = tool.count_copies(text, stores + [n // cfg.num_layers
                                              for n in stores])
    assert found["remat"] == {} and found["whole_store_copies"] == {}
    held = sum(math.prod(x.shape) * x.dtype.itemsize for x in pool.values())
    stats = compiled.memory_analysis()
    assert held <= stats.alias_size_in_bytes < 1.0001 * held
    # the scores and the choice of a chunk tick are the largest things a
    # tick holds: 2 x 4 B x rows x 18,432 a layer in flight and no more
    # (the choice is a Mosaic call that reads the scores and writes the
    # mask: no words, halves or counts of XLA's beside them), what the
    # engine reserves for them (``CacheKind.tick_bytes`` a layer)
    assert stats.temp_size_in_bytes < 8 * rows * 18432 + (176 << 20)
    # the three Mosaic calls a layer, by the names the benchmark reads
    assert "%index_scores" in text and "%sparse_attention" in text
    assert "%sparse_choice" in text


# (rows of the tick bucket, table tier) of the cell of sparse layers: 32
# query heads on 4 KV heads of 128; the indexer's 16 heads against index
# keys stored 128 wide, 6 layers x 4,353 blocks of 128, ONE table a slot
SPARSE_SHAPES = {f"keye-vl2-{rows}x{tier}": (rows, tier)
                 for rows in (256, 2048) for tier in (36, 72, 144)}


@pytest.mark.parametrize("shape", sorted(SPARSE_SHAPES))
def test_sparse_layer_kernels_compile_for_v5e(one_chip, shape):
    from deepspeed_tpu.ops.pallas.index_scores import index_scores
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_attention,
                                                          step_positions)
    from deepspeed_tpu.ops.pallas.sparse_choice import sparse_choice

    T, MB = SPARSE_SHAPES[shape]
    NB, bf = 6 * 4353, jnp.bfloat16

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = jax.jit(
        lambda q, w, store, t, n, s: index_scores(q, w, store, t, n, s,
                                                  interpret=False)
    ).lower(arg((T, 16, 128), bf), arg((T, 16), bf), arg((NB, 128, 128), bf),
            arg((29, MB), jnp.int32), arg((T,), jnp.int32),
            arg((T,), jnp.int32)).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%index_scores" in text
    steps = MB                      # blocks of 128: a lane tile a block
    # the choice: a tile of 32 rows' scores, their words and the mask in
    # VMEM at once (12 MB at 144 blocks)
    text = jax.jit(
        lambda scores, n: sparse_choice(scores, n, 2048, interpret=False)
    ).lower(arg((steps, T, 128), jnp.float32),
            arg((T,), jnp.int32)).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%sparse_choice" in text
    pool = arg((NB, 128, 4, 128), bf)

    def lowered(choice):
        return jax.jit(
            lambda q, k, v, t, n, s, c: paged_attention(
                q, k, v, t, n, interpret=False, name="sparse_attention",
                mxu_dtype=bf, row_table=s, chosen=c)
        ).lower(arg((T, 32, 128), bf), pool, pool, arg((29, MB), jnp.int32),
                arg((T,), jnp.int32), arg((T,), jnp.int32),
                arg((steps, T, 128), choice))

    # a step carries two blocks of 128 and reads two planes of the choice
    # (10.54 MiB of scoped VMEM at 2,048 x 144, of the 16 MiB default: the
    # call sets no limit)
    assert step_positions(arg((T, 32, 128), bf), pool, pool) == 256
    text = lowered(jnp.float32).compile().as_text()
    # one Mosaic call, the pool still at operand 3, the choice behind it
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%sparse_attention" in text
    if T == 256:
        # a row alone reads its plane of the choice at a run-time sublane,
        # which a 16-bit plane refuses: the choice is float32
        with pytest.raises(Exception, match="multiple of 8"):
            lowered(bf).compile()


@pytest.mark.parametrize("kernel", ["latent", "global"])
def test_bfloat16_products_are_pinned_whatever_the_context(one_chip, kernel):
    """``jax.default_matmul_precision("highest")`` around a call (the
    float32 witness of a probe sets it) reached the kernel's products of
    bfloat16 operands, which Mosaic then refused (``Bad lhs type``: PERF.md
    section 7, of PRs 41-43). Their precision is pinned inside the kernel:
    the served type's program is the same text with the context and
    without."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        latent_paged_attention, paged_attention)

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf, ints = jnp.bfloat16, (arg((29, 128), jnp.int32),
                              arg((256,), jnp.int32), arg((256,), jnp.int32))
    if kernel == "latent":
        fn = jax.jit(lambda q, pool, t, n, w: latent_paged_attention(
            q, pool, t, n, 512, 192 ** -0.5, interpret=False, row_table=w))
        args = (arg((256, 32, 640), bf), arg((8192, 32, 640), bf)) + ints
    else:
        fn = jax.jit(lambda q, k, v, t, n, w: paged_attention(
            q, k, v, t, n, interpret=False, name="global_attention",
            mxu_dtype=bf, row_table=w))
        pool = arg((12288, 32, 8, 128), bf)
        args = (arg((256, 48, 128), bf), pool, pool) + ints
    # (a Mosaic call serialises its body with its call stack, and the
    # ``with`` below is a line of it)
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        plain = fn.lower(*args).compile().as_text()
        jax.clear_caches()
        with jax.default_matmul_precision("highest"):
            under = fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    # (but for what of the text is its source's: metadata, names)
    same = _copies_tool().normalised
    assert same(plain) == same(under)


@pytest.mark.parametrize("program", sorted(TICK_PROGRAMS))
def test_a_tick_re_lays_no_state_store(one_chip, program):
    import math

    tool = _copies_tool()
    cell, rows, tier, store = TICK_PROGRAMS[program]
    cfg, sizes, programs = tool.cell_programs(cell)
    assert (rows, tier) in programs
    lowered, pool = tool.lower_tick(cfg, sizes, rows, tier, one_chip)
    compiled = lowered.compile()
    found = tool.count_copies(compiled.as_text(),
                              [math.prod(pool[store].shape)])
    assert found["remat"] == {} and found["whole_store_copies"] == {}
    # the whole pool rides the tick in place (and pads next to nothing: a
    # store's rows up to a multiple of 8)
    held = sum(math.prod(x.shape) * x.dtype.itemsize for x in pool.values())
    assert held <= compiled.memory_analysis().alias_size_in_bytes \
        < 1.0001 * held


def test_a_looped_tick_holds_its_layers_once(one_chip):
    """The 64-row decode tick of the looped cell at its real size: 192
    layer applications over ONE set of leaves. The program's arguments are
    the weights (5.34 GB) and the pool (9.71 GB: 192 cache layers of 193
    blocks), the pool rides in place, and no layer's weights are copied a
    PASS: what the tick holds beside its arguments is XLA's one re-laid
    copy a TICK of three of the square projection leaves (``bf16[48, 2048,
    2048]``, 403 MB each, minor dimensions exchanged: with one pass it
    re-lays a layer's slice on its way into VMEM instead; PERF.md, PR 55).
    Four scans, one ``paged_attention`` call each."""
    import math

    tool = _copies_tool()
    cfg, sizes, programs = tool.cell_programs("serve-ouro-2.6b-cot-closed")
    assert (cfg.loop_passes, cfg.num_layers) == (4, 48)
    assert (64, 16) in programs and (512, 4) in programs
    lowered, pool = tool.lower_tick(cfg, sizes, 64, 16, one_chip)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert pool["k"].shape == (192, 193, 32, 16, 128)
    held = sum(math.prod(x.shape) * x.dtype.itemsize for x in pool.values())
    weights = 2 * cfg.num_params()
    stats = compiled.memory_analysis()
    assert held == 9_714_008_064 and weights == 5_335_949_314
    assert held <= stats.alias_size_in_bytes < 1.0001 * held
    assert held + weights <= stats.argument_size_in_bytes \
        < 1.001 * (held + weights)
    assert stats.temp_size_in_bytes < 1.25e9
    found = tool.count_copies(text, [math.prod(pool["k"].shape)])
    assert found["remat"] == {} and found["whole_store_copies"] == {}
    assert sum(found["copies"].values()) <= 3
    assert len(_mosaic_calls(text)) == 4
    assert text.count("%paged_attention") >= 4


def test_the_copy_count_sees_what_it_is_for():
    """``count_copies`` on the lines the parent's decode tick held."""
    tool = _copies_tool()
    text = "\n".join([
        "  %copy.976 = bf16[6,273,3,12288]{3,2,1,0:T(4,128)(2,1)} "
        "copy(%param.3)",
        "  %fusion.207.remat_compressed = bf16[1638,3,12288]"
        "{2,0,1:T(8,128)(2,1)} fusion(%x), kind=kLoop",
        "  %reshape.1 = bf16[18,273,12288]{2,1,0:T(8,128)(2,1)} "
        "reshape(%fusion.268)",
        "  ROOT %copy.2 = bf16[256,3,12288]{2,1,0:T(4,128)(2,1)S(1)} "
        "copy(%y)",
        "  %bitcast.7 = bf16[1638,3,12288]{2,1,0:T(4,128)(2,1)} "
        "bitcast(%param.3)"])
    found = tool.count_copies(text, [6 * 273 * 3 * 12288], min_bytes=16 << 20)
    assert found["remat"] == {"bf16[1638,3,12288]{2,0,1:T(8,128)(2,1)}": 1}
    assert sum(found["whole_store_copies"].values()) == 2   # copy, reshape
    assert found["copies"] == {
        "bf16[6,273,3,12288]{3,2,1,0:T(4,128)(2,1)}": 1,
        "bf16[256,3,12288]{2,1,0:T(4,128)(2,1)S(1)}": 1}
