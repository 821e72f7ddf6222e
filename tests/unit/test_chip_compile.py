"""Kernels of the serving path compiled ALONE for the v5e at the benchmark's
widths, without a chip: the TPU's compiler is installed here and compiles
for a described topology. What Mosaic refuses (a slice off the tiling, too
much VMEM, an unsupported relayout) interpret mode never shows; this does,
at no chip time. Nothing runs, so it says nothing about results or speed.

Whole tick programs are ``test_chip_compile_ticks.py``'s and the training
step's kernels ``test_chip_compile_training.py``'s: three files, so that
the compiles spread over three workers. Keep every such compile in one of
the three: the topology is described inside ``chip_topology.one_chip``, so
only a worker that is handed one of them loads libtpu.
"""
import pytest

import jax
import jax.numpy as jnp

from chip_topology import one_chip  # noqa: F401 (a fixture)
from family_harness import load_tool


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


# (rows of the tick bucket, groups, rows of a chunk) of the two cells whose
# ``mamba2`` layers take the chunked form, each at its two buckets: 128
# heads of 64 x 128, eight groups in chunks of 128 (``nemotron_h``) and one
# group in chunks of 256 (``granitemoehybrid``)
SSD_CHUNK_SHAPES = {"nemotron3-2048": (2048, 8, 128),
                    "nemotron3-128": (128, 8, 128),
                    "granite4-2048": (2048, 1, 256),
                    "granite4-256": (256, 1, 256)}


@pytest.mark.parametrize("shape", sorted(SSD_CHUNK_SHAPES))
def test_ssd_chunk_compiles_for_v5e(one_chip, shape):
    """The chunked form at a cell's chunk bucket and at one chunk: ONE
    Mosaic call under its own name (``benchmarks/roofline/ssd_chunk.py``
    finds it by the scope), a grid as long as the tick's pieces, the store
    aliased through the loop of one trip and the call, and nothing of
    ``[chunks, heads, L, L]`` or ``[rows, heads, channels]`` made beside
    it."""
    from deepspeed_tpu.models import hybrid as HY
    from deepspeed_tpu.ops.pallas.ssd import ssd_chunk

    rows, groups, chunk = SSD_CHUNK_SHAPES[shape]

    def arg(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def form(x, d, g, B, C, state, slot, positions):
        return ssd_chunk(x, d, g, B, C, HY.runs_of(slot, positions),
                         slot > 0, state, slot, chunk, interpret=False)

    store = 5 * 137 * 64 * 128 * 128 * 4
    compiled = jax.jit(form, donate_argnums=(5,)).lower(
        arg((rows, 128, 64), jnp.float32), arg((rows, 128), jnp.float32),
        arg((rows, 128), jnp.float32), arg((rows, groups, 128), jnp.float32),
        arg((rows, groups, 128), jnp.float32),
        arg((5 * 137, 64, 128, 128), jnp.float32),
        arg((rows,), jnp.int32), arg((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%ssd_chunk" in text
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= store
    # beside the result [rows, 128, 64]: the call's own result before its
    # rows' mask (alone here; a tick's mixer masks it where it reads it),
    # the pieces' scalars and masks (1 GB was allowed the plain form's sums
    # within the chunks)
    assert stats.temp_size_in_bytes < rows * 128 * 64 * 4 + (4 << 20)


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
    same = load_tool("tick_program_copies").normalised
    assert same(plain) == same(under)
