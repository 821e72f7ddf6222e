"""The ``KeyeVL2`` family's files (configuration ``keye-vl-2.0-30b-a3b``,
cell ``serve-keye-vl2-30b-longctx-closed``, mix ``longctx-closed``): loaded
by name, held to the numbers of the issue that asked for them (ISSUE 46)
and to the catalog's row, the reference against the program at the
rehearsal size, the warm-up against every program a window can meet, the
flops counter and the two rooflines' needs by hand, and the seven new
readers on a made-up run record.

What a family needs beside its configuration, by name: a ``reference``
(``arch_from_config``, ``forward_logits``, ``next_token_loss``), a ``flops``
counter (``train_flops_per_token``), a cell file, a mix, and a reader a
per-layer metric it brings. How many families, cells or metrics there are
is nobody's to assert here.
"""
import dataclasses
import json

import numpy as np
import pytest

from benchmarks import harness, manifest, model_config, weights
from benchmarks.flops import keye_sparse as keye_flops
from benchmarks.layer_metrics import (index_scores_roofline, index_share_pct,
                                      select_share_pct,
                                      sparse_attention_roofline,
                                      sparse_attention_share_pct,
                                      sparse_read_excess,
                                      sparse_selected_share_pct)
from benchmarks.roofline import index_scores, sparse_attention, tick_attrs

M = manifest.load_manifest()
CELL = "serve-keye-vl2-30b-longctx-closed"
CONFIG = "keye-vl-2.0-30b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW = {"index_scores_roofline", "sparse_attention_roofline",
       "index_share_pct", "select_share_pct", "sparse_attention_share_pct",
       "sparse_selected_share_pct", "sparse_read_excess"}

# the two Mosaic calls of a sparse layer in the (256, 576) tick as compiled
# for a v5e at the cell's sizes
INDEX = ('%index_scores.3 = f32[144,256,128]{2,1,0} custom-call(s32[29,144]'
         '{1,0} %t, s32[768]{0} %m, bf16[256,16,128]{2,1,0} %q, f32[256,16]'
         '{1,0} %w, f32[256,512]{1,0} %wd, bf16[26118,128,128]{2,1,0} %idx), '
         'custom_call_target="tpu_custom_call"')
WALK = ('%sparse_attention.5 = bf16[256,32,128]{2,1,0} custom-call(s32[29,'
        '192]{1,0} %t, s32[768]{0} %m, bf16[256,32,128]{2,1,0} %q, bf16['
        '26118,128,4,128]{3,2,1,0} %k, bf16[26118,128,4,128]{3,2,1,0} %v, '
        'f32[144,256,128]{2,1,0} %c), custom_call_target="tpu_custom_call"')


class _Op:
    is_mosaic = True

    def __init__(self, text, seconds=1e-3, at=0.0):
        self.text, self.seconds = text, seconds
        self.start, self.end = at, at + seconds
        self.name = text.split(" ", 1)[0].lstrip("%")


class _Trace:
    chips = [0]

    def __init__(self, ops):
        self._ops = ops

    def busy_s(self):
        return sum(o.seconds for o in self._ops)

    def ops_in_window(self, chip):
        return self._ops

    def op_seconds(self, pred):
        return sum(o.seconds for o in self._ops if pred(o))


def _served_config():
    return model_config.build(manifest.load_cell(CELL).config, "serve")


# ------------------------------------------------------------------ #
def test_cell_config_and_mix_load_by_name_with_the_issues_numbers():
    cell = manifest.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.runner) \
        == (CONFIG, "longctx-closed", 1, "serve")
    eng = cell.deploy["engine"]
    # ISSUE 46 reckoned with blocks of 32; blocks of 128 read 11 % more
    # tokens a second (a walk's copies are a quarter as many: the cell's
    # notes have both runs)
    assert (eng["block_size"], eng["token_budget"]) == (128, 2048)
    assert eng["max_blocks_per_seq"] * eng["block_size"] >= 576 * 32
    p = cell.traffic["params"]
    assert cell.traffic["generator"] == "closed_loop"
    assert (p["clients"], p["preroll_s"]) == (24, 10)
    assert p["prompt_tokens"]["dist"] == p["output_tokens"]["dist"] \
        == "uniform"
    # ISSUE 46's ranges, or the same halved about their centres (its one
    # pre-stated step, which the mix's notes then carry the numbers for)
    ranges = ((p["prompt_tokens"]["min"], p["prompt_tokens"]["max"]),
              (p["output_tokens"]["min"], p["output_tokens"]["max"]))
    assert ranges in (((15360, 17408), (576, 704)),
                      ((15872, 16896), (608, 672)))
    if ranges[0] != (15360, 17408):
        assert "halved" in cell.traffic["notes"]
    # the longest sequence fits a table; every client has a slot; all 24 at
    # their longest stay under the 0.80 watermark at which the frontend
    # degrades a request
    longest = ranges[0][1] + ranges[1][1]
    assert longest <= 18112 <= eng["max_blocks_per_seq"] * eng["block_size"]
    assert eng["state_slots"] >= p["clients"]
    assert 24 * -(-longest // eng["block_size"]) \
        / (eng["n_blocks"] - 1) <= 0.80
    # a row chooses 2,048 of 7.5 to 8.8 times as many
    assert 7.4 < ranges[0][0] / 2048 and longest / 2048 < 8.9
    row = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    conf = cell.config
    assert conf["as_run"]["serve"] == {"num_hidden_layers": 6}
    assert set(conf["published"]) == set(row["reduced"])
    assert conf["deployment"]["chips_that_share_a_layer"] == 8
    assert {m.name for m in cell.end_to_end} == {"serve_out_tokens_per_s",
                                                 "setup_s"}
    names = {m.name for m in cell.per_layer}
    assert NEW | {"experts_share_pct", "held_expert_gmm_roofline",
                  "expert_held_pair_share_pct", "expert_rows_per_held_expert",
                  "expert_load_imbalance", "state_slots_peak_pct",
                  "closed_itl_p50_ms", "closed_ttft_p50_ms",
                  "closed.decode_rows_per_tick", "closed.device_idle_pct",
                  "closed.hbm_peak_gb", "closed.win_ticks_per_s"} <= names
    # readers of another family's kernels and shares do not hold here
    assert not names & {"closed.paged_share_pct", "swa_attention_roofline",
                        "global_attention_roofline", "ssm_share_pct",
                        "conv_share_pct", "kda_share_pct",
                        "latent_attention_roofline", "expert_gmm_roofline"}
    for m in M["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tokens_per_s"
    spec = cell.deploy["logits_check"]
    # a prompt of three times ``topk`` in a stream of 2,048-row ticks and
    # one that never chooses, cut by a tick's end; then decode ticks
    a, b = spec["prompt_lens"]
    assert a >= 3 * 2048 and b < 2048 and a % 2048 + b > 0
    assert spec["decode_steps"] >= 15


def test_the_limit_lies_between_its_readings():
    """Over every reading of the system, under the reference computed in
    float8_e4m3, with room on both sides; each mistake of the issue's list
    either fails the limit or is named as held by a CPU test."""
    spec = manifest.load_cell(CELL).deploy["logits_check"]
    got = spec["chip_readings"]
    system = list(got["system"].values())
    assert len(system) >= 8 and max(system) == got["system_max"]
    lower = min(got["reference_computed_in_float8_e4m3"].values())
    tol = spec["rel_tol"]
    assert 1.2 * max(system) < tol < lower / 1.2
    assert got["system_float32_highest"] < 1e-4
    seen = {k for k, v in got["system_against_a_mistaken_reference"].items()
            if v > tol}
    unseen = set(got["the_check_cannot_see"])
    reference = manifest.load_plugin("reference", "keye_sparse_lm")
    assert seen | unseen >= set(reference.FAULTS)
    for name, test in got["the_check_cannot_see"].items():
        assert test.startswith("tests/unit/test_keye_sparse_stack.py::")


def test_the_file_holds_every_number_of_the_catalog():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    conf = manifest.load_cell(CELL).config
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    # no width among the keys changed
    assert not {k for k in entry["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"}
    assert set(conf["assumed"]) >= {
        "per-head q/k norms", "indexer queries", "indexer key",
        "indexer weights", "indexer rotary", "the choice",
        "q_chunk_size / kv_chunk_size"}


def test_served_model_is_the_share_the_file_describes():
    import jax

    from deepspeed_tpu.models import paged as PG

    cfg = _served_config()
    eng = manifest.load_cell(CELL).deploy["engine"]
    assert cfg.layer_kinds == ("sparse",) * 6
    assert (cfg.n_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.moe_first_expert) == (16, 128, 8, 0)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
            cfg.head_dim) == (18992, 2048, 32, 4, 128)
    assert (cfg.sparse_topk, cfg.index_heads, cfg.index_head_dim) \
        == (2048, 16, 64)
    assert round(cfg.num_params() / 1e6) == 659
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, eng["n_blocks"], eng["block_size"],
        state_slots=eng["state_slots"], max_run=eng["token_budget"]))
    size = {k: int(np.prod(v.shape)) * v.dtype.itemsize
            for k, v in pool.items()}
    # a position: 2,048 B of keys and values and an index key stored 256 B
    # wide (128 B of values), a layer
    per_position = 6 * eng["n_blocks"] * eng["block_size"]
    assert (size["k"] + size["v"]) // per_position == 2048
    assert size["idx"] // per_position == 256 == 2 * PG.index_row_width(cfg)
    # weights + pool: over a quarter of the chip
    assert (2 * cfg.num_params() + sum(size.values())) / 16e9 > 0.25
    # what a chunk tick's scores and choice take is reckoned
    kind = PG.cache_kinds(cfg)["sparse"]
    assert kind.tick_bytes(2048, 18432) == 2 * 4 * 2048 * 18432


def test_warmup_reaches_every_bucket_and_tier_of_the_cell():
    """One request at a time: a prompt of n tokens runs chunks of 2,048
    rows (a chunk that fits the 256-row bucket runs there), then decode
    ticks; the window's ticks are those programs and no other."""
    cell = manifest.load_cell(CELL)
    eng = cell.deploy["engine"]
    bs, budget = eng["block_size"], eng["token_budget"]
    small = budget // 8
    tiers = [eng["max_blocks_per_seq"] // 4, eng["max_blocks_per_seq"] // 2,
             eng["max_blocks_per_seq"]]

    def tier(pos):
        return next(t for t in tiers if pos // bs + 1 <= t)

    seen = set()
    for n in cell.deploy["warmup"]["prompt_lens"]:
        at = 0
        while at < n:
            rows = min(budget, n - at)
            seen.add((small if rows <= small else budget,
                      tier(at + rows - 1)))
            at += rows
        seen.add((small, tier(n)))
    assert seen == {(b, t) for b in (small, budget) for t in tiers}
    p = cell.traffic["params"]
    longest = p["prompt_tokens"]["max"] + p["output_tokens"]["max"]
    assert tier(longest - 1) == tiers[-1]


# ------------------------------------------------------------------ #
def _toy():
    conf = dict(manifest.load_cell(CELL).config)
    cfg = dataclasses.replace(
        model_config.build(conf, "serve", rehearse=True), dtype="float32")
    hf = {**model_config.hf_kwargs(conf, "serve"), **conf["rehearse"]}
    reference = manifest.load_plugin("reference", conf["reference"])
    return cfg, reference, reference.arch_from_config(conf, hf)


def test_the_named_reference_agrees_with_the_program_forward():
    """The rehearsal size keeps the cut's pattern with ``topk`` 32, shorter
    than the sequences; the weights are the benchmark's own (norm gains off
    one, the index key's LayerNorm bias off zero)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    cfg, reference, arch = _toy()
    assert cfg.layer_kinds == ("sparse",) * 6
    assert (cfg.n_experts, cfg.router_experts, cfg.sparse_topk) \
        == (4, 16, 32)
    params = weights.init_on_device(cfg, 3)
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 80)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks), cfg)
    want = reference.forward_logits(params, toks, arch)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    some = reference.forward_logits(params, toks, arch, at=[79, 4, 5])
    np.testing.assert_allclose(some, want[:, np.asarray([79, 4, 5])],
                               rtol=1e-6, atol=1e-6)
    sets = []
    reference.forward_logits(params, toks[:1], arch, chosen=sets)
    assert len(sets) == 6 and sets[0].shape == (80, 80)
    np.testing.assert_array_equal(np.asarray(sets[3]).sum(1),
                                  np.minimum(np.arange(80) + 1, 32))
    loss = reference.next_token_loss(params, toks, arch)
    assert abs(loss - np.log(cfg.vocab_size)) < 1.0


def test_the_reference_imports_nothing_of_the_program():
    _, reference, arch = _toy()
    with open(reference.__file__) as f:
        source = f.read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert (arch["topk"], arch["index_heads"], arch["index_dim"]) \
        == (32, 4, 8)
    for name in ("arch_from_config", "forward_logits", "next_token_loss"):
        assert callable(getattr(reference, name))
    with pytest.raises(ValueError, match="model_type"):
        reference.arch_from_config({"model_type": "llama"}, {})


def test_keye_flops_by_hand():
    cfg = _served_config()
    h = 2048
    attn = h * 4096 + 2 * h * 512 + 4096 * h
    index = h * 16 * 64 + h * 64 + h * 16
    ffn = 3 * h * 8 * 768 + h * 128
    want = 18992 * h + 6 * (attn + index + ffn)
    assert keye_flops.active_matmul_params(cfg) == want
    # 8,192 positions: the first 2,048 attend to all they have, the others
    # to 2,048; the indexer scores every one
    attended = (2048 * 2049 / 2 + (8192 - 2048) * 2048) / 8192
    assert keye_flops.attended_positions(cfg, 8192) == pytest.approx(attended)
    assert keye_flops.attended_positions(cfg, 1024) == pytest.approx(512.5)
    assert keye_flops.indexed_positions(8192) == 4096.5
    assert keye_flops.train_flops_per_token(cfg, 0, 8192) == pytest.approx(
        6.0 * want + 3.0 * 6 * (4 * 32 * 128 * attended
                                + 2 * 16 * 64 * 4096.5))


def test_the_rooflines_needs_by_hand():
    cfg = _served_config()
    assert index_scores.key_bytes(cfg) == 128
    assert index_scores.pair_ops(cfg) == 2 * 16 * 64
    assert sparse_attention.position_bytes(cfg) == 2048
    assert sparse_attention.pair_ops(cfg) == 16384

    class Run:
        peaks, model, trace, cache = PEAKS, cfg, None, {}

    import benchmarks.roofline.tick_attrs as TA
    # a decode tick of 24 rows at 17,000 and a chunk tick of 24 decode rows
    # and 2,024 rows of one prompt from position 14,000 on
    chunk = sum(range(14001, 14001 + 2024))
    ticks = [
        {"start": 1.0, "end": 1.1, "sparse_layers": 6,
         "index_positions": 6 * 24 * 17000,
         "index_walk_positions": 6 * 24 * 17000,
         "sparse_selected": 6 * 24 * 2048,
         "sparse_selected_decode": 6 * 24 * 2048},
        {"start": 1.2, "end": 1.4, "sparse_layers": 6,
         "index_positions": 6 * (24 * 17000 + chunk),
         "index_walk_positions": 6 * (24 * 17000 + 16024),
         "sparse_selected": 6 * (24 + 2024) * 2048,
         "sparse_selected_decode": 6 * 24 * 2048}]
    real = TA.per_tick
    TA.per_tick = lambda run: ticks
    try:
        calls = [_Op(INDEX, at=1.0 + i * 1e-3) for i in range(6)] \
            + [_Op(INDEX, at=1.2 + i * 1e-3) for i in range(6)]
        seconds, bound = index_scores.least_seconds(Run, calls)
        # the decode tick is bound by its keys' bytes, the chunk tick by
        # its rows' products
        mem = 6 * 24 * 17000 * 128 / 819e9
        ops = 6 * (24 * 17000 + chunk) * 2048 / 197e12
        assert ops > 6 * (24 * 17000 + 16024) * 128 / 819e9
        assert seconds == pytest.approx(mem + ops) and bound == "compute"
        seconds, bound = sparse_attention.least_seconds(Run, calls)
        decode = 6 * 24 * 2048 * 2048 / 819e9
        rows = 6 * 2024 * 2048 * 16384 / 197e12
        assert rows > decode
        assert seconds == pytest.approx(decode + rows)
        # half the calls of a tick in the window: half its need
        seconds, _ = index_scores.least_seconds(Run, calls[:9])
        assert seconds == pytest.approx(mem + ops / 2)
    finally:
        TA.per_tick = real
    assert index_scores.classify(_Op(INDEX)) == "index"
    assert index_scores.classify(_Op(WALK)) is None


def test_the_new_readers_on_a_made_up_run(monkeypatch):
    proj = ('%fusion.7 = bf16[256,1024]{1,0} fusion(bf16[256,2048]{1,0} '
            '%a), kind=kOutput')
    count = ('%fusion.8 = s32[1,224,1]{2,1,0} fusion(u16[144,224,128]'
             '{2,1,0} %u), kind=kInput')
    gather = ('%gather.2 = bf16[32,2048,4,128]{3,2,1,0} gather(bf16[3342528,'
              '4,128]{2,1,0} %k, s32[32,2048]{1,0} %i)')
    other = '%fusion.9 = bf16[256,2048]{1,0} fusion(bf16[256,2048]{1,0} %b)'

    class Op(_Op):
        is_mosaic = False

    ops = [Op(proj, 1e-3, at=1.0), _Op(INDEX, 2e-3, at=1.01),
           Op(count, 1e-3, at=1.02), Op(gather, 1e-3, at=1.03),
           _Op(WALK, 3e-3, at=1.04), Op(other, 2e-3, at=1.05)]
    from benchmarks import gap_chain

    monkeypatch.setattr(gap_chain, "trace_file", lambda run: "x.pb")
    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (0, proj): "jit(tick)/while/body/attn/index/dot_general",
        (0, INDEX): "jit(tick)/while/body/attn/index/index_scores",
        (0, count): "jit(tick)/while/body/attn/select/cond/while/reduce_sum",
        (0, gather): "jit(tick)/while/body/attn/sparse/gather",
        (0, WALK): "jit(tick)/while/body/attn/sparse/sparse_attention",
        (0, other): "jit(tick)/while/body/experts/gmm"})
    tick = {"start": 1.0, "end": 1.1, "blocks": 12000, "prompt_attended": 0,
            "sparse_layers": 6, "index_positions": 6 * 24 * 17000,
            "index_walk_positions": 6 * 24 * 17000,
            "sparse_selected": 6 * 24 * 2048,
            "sparse_selected_decode": 6 * 24 * 2048,
            "sparse_positions_read": 6 * 24 * 2048}
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [tick])

    class Run:
        peaks, model = PEAKS, _served_config()
        trace = _Trace(ops)
        telemetry = harness.Telemetry(*[{"counters": {}, "gauges": {},
                                         "histograms": {}}] * 2)
        extras, cache = {}, {}

    assert index_share_pct.read(Run) == pytest.approx(100 * 3e-3 / 10e-3)
    assert select_share_pct.read(Run) == pytest.approx(100 * 1e-3 / 10e-3)
    assert sparse_attention_share_pct.read(Run) == pytest.approx(
        100 * 4e-3 / 10e-3)
    assert sparse_selected_share_pct.read(Run) == pytest.approx(
        100 * 2048 / 17000)
    assert sparse_read_excess.read(Run) == 1.0
    # one call of six in the tick's run: a sixth of its need over its time
    assert index_scores_roofline.read(Run) == pytest.approx(
        100 * 24 * 17000 * 128 / 819e9 / 2e-3)
    # the gather and the walk are both the attention over the chosen
    assert sparse_attention_roofline.read(Run) == pytest.approx(
        100 * 6 * 24 * 2048 * 2048 / 819e9 / 4e-3)
    assert Run.extras["roofline_bound"] == {"index_scores": "memory",
                                            "sparse_attention": "memory"}
    # rows that walk under a mask read more than they chose
    tick["sparse_positions_read"] = 6 * 24 * 17000
    assert sparse_read_excess.read(Run) == pytest.approx(17000 / 2048)

    # the parent's program, or any other model's: no such scope, call or
    # attribute -> nothing, and nothing raises
    class Parent(Run):
        trace = _Trace([Op(other, 1e-3)])
        extras, cache = {}, {}

    monkeypatch.setattr(gap_chain, "op_scopes", lambda path: {
        (0, other): "jit(tick)/while/body/experts/gmm"})
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [
        {"start": 0.0, "end": 1.0, "blocks": 10, "prompt_attended": 3}])
    readers = (index_scores_roofline, sparse_attention_roofline,
               index_share_pct, select_share_pct, sparse_attention_share_pct,
               sparse_selected_share_pct, sparse_read_excess)
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
    Parent.trace, Parent.cache = None, {}
    monkeypatch.setattr(gap_chain, "trace_file", lambda run: None)
    monkeypatch.setattr(tick_attrs, "per_tick", lambda run: [])
    for reader in readers:
        assert reader.read(Parent) is None, reader.__name__
