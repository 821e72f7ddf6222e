"""Whole tick programs of the serving cells compiled for the v5e at their
real sizes, without a chip (``tools/tick_program_copies.py``: the one place
that compiles a cell's tick and counts its copies). The kernels alone are
``test_chip_compile.py``'s; see its note on libtpu.
"""
import pytest

from chip_topology import mosaic_calls as _mosaic_calls
from chip_topology import one_chip  # noqa: F401 (a fixture)
from family_harness import load_tool


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

    tool = load_tool("tick_program_copies")
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


@pytest.mark.parametrize("program", sorted(TICK_PROGRAMS))
def test_a_tick_re_lays_no_state_store(one_chip, program):
    import math

    tool = load_tool("tick_program_copies")
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

    tool = load_tool("tick_program_copies")
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
    tool = load_tool("tick_program_copies")
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


def test_the_copy_count_sees_a_run_of_layers_cut_ahead_of_its_loop():
    """``weight_copies`` on the lines the paired cell's decode tick held
    before PR 62 (a run of four ``mamba2`` layers sliced out of the leaves
    ahead of its scan), and beside them what it must NOT count: a product
    that takes a parameter and rows, a prefetch into another memory space,
    a slice under the size asked, an instruction outside the entry."""
    tool = load_tool("tick_program_copies")
    w_in = "%params__blocks____mamba2____w_in__.1"
    text = "\n".join([
        "%fused_computation.882 (param_0.1: bf16[9,4096,16768]) -> "
        "bf16[4,1,4096,16768] {",
        "  %slice.1 = bf16[4,1,4096,16768]{3,2,1,0:T(8,128)(2,1)} "
        f"slice({w_in})",
        "}",
        "ENTRY %main.413 (params__blocks____mamba2____w_in__.1: "
        "bf16[9,4096,16768]) -> bf16[256,4096] {",
        f"  {w_in} = bf16[9,4096,16768]{{2,1,0:T(8,128)(2,1)}} parameter(13)"
        ", sharding={replicated}",
        "  %params__blocks____attn____wo__.1 = bf16[1,4096,4096]"
        "{2,1,0:T(8,128)(2,1)} parameter(1)",
        "  %slice_bitcast_fusion = bf16[4,1,4096,16768]"
        f"{{3,2,1,0:T(8,128)(2,1)}} fusion({w_in}), kind=kLoop, "
        "calls=%fused_computation.882",
        "  %slice_bitcast_fusion.12 = bf16[4,1,128]{2,0,1:T(4,128)(2,1)} "
        f"fusion({w_in}), kind=kLoop, calls=%fused_computation.3006",
        "  %fusion.623 = bf16[2048,16768]{1,0:T(8,128)(2,1)} "
        f"fusion({w_in}, %fusion.61), kind=kOutput, "
        "calls=%fused_computation.886",
        "  %bitcast.2778 = bf16[4096,4096]{1,0:T(8,128)(2,1)} "
        "bitcast(%params__blocks____attn____wo__.1)",
        "  %copy.860 = bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} "
        "copy(%bitcast.2778)",
        "  %copy.861 = bf16[4096,4096]{0,1:T(8,128)(2,1)} "
        "copy(%bitcast.2778)",
        "}"])
    assert tool.count_copies(text)["weight_copies"] == {
        "params/blocks/mamba2/w_in -> "
        "bf16[4,1,4096,16768]{3,2,1,0:T(8,128)(2,1)}": 1,
        "params/blocks/attn/wo -> bf16[4096,4096]{0,1:T(8,128)(2,1)}": 1}
