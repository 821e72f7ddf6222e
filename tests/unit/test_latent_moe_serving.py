"""Moonlight-16B-A3B's block (``deepseek_v3``: latent attention, one leading
dense layer, routed experts beside shared ones) through the normal path, at
toy width: 1 dense + 2 expert layers, 8 experts top-2, 1 shared, blocks of
8 positions, tables of 64 blocks. The oracle is the benchmark's plain
reference (``benchmarks/reference/deepseek_lm.py``), which imports nothing
of the program. (No ``Family`` table: the toy model is the configuration
file's own ``rehearse`` widths under the benchmark's weights, one model.)
"""
import dataclasses
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import model_config, weights
from family_harness import drive, rel
from benchmarks.reference import deepseek_lm as R
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf
from deepspeed_tpu.ops.pallas.paged_attention import (_geometry,
                                                      latent_paged_attention,
                                                      paged_attention,
                                                      tile_rows)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BS, MB = 8, 64


def _config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "moonlight-16b-a3b.json")) as f:
        return json.load(f)


def _toy(**over):
    """(program config in float32, the reference's arch, source keys)."""
    cf = _config_file()
    hf = model_config.hf_kwargs(cf, "serve")
    hf.update(cf["rehearse"])
    hf.update(over)
    cfg = dataclasses.replace(
        config_from_hf(types.SimpleNamespace(**hf)), dtype="float32")
    return cfg, R.arch_from_config(cf, hf), hf


@pytest.fixture(scope="module")
def toy():
    cfg, arch, _ = _toy()
    return cfg, arch, weights.init_on_device(cfg, 3)


# ------------------------------------------------------------------ #
# the model on the normal path
# ------------------------------------------------------------------ #
def test_config_from_hf_on_the_published_keys():
    cf = _config_file()
    hf = {k: v for k, v in cf.items() if k not in model_config.OWN_KEYS}
    hf["num_hidden_layers"] = cf["published"]["num_hidden_layers"]
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    assert (cfg.num_layers, cfg.first_dense_layers) == (27, 1)
    assert [(k, c.num_layers, c.n_experts) for k, c in cfg.segments] == [
        ("dense_blocks", 1, 0), ("blocks", 26, 64)]
    assert (cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (
        2048, 16, 163840)
    assert cfg.mla and cfg.q_lora_rank is None
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.ffn_size, cfg.moe_ffn, cfg.moe_shared_size) == (
        11264, 1408, 2 * 1408)
    assert (cfg.moe_top_k, cfg.moe_score_func, cfg.moe_gate_bias,
            cfg.moe_route_norm, cfg.moe_route_scale) == (
        6, "sigmoid", True, True, 2.446)
    assert cfg.rope_theta == 50000.0 and cfg.max_seq_len == 8192
    assert not cfg.tie_embeddings
    # 26 x 585 M + 83 M + 671 M: the published 16 B
    assert 15.9e9 < cfg.num_params() < 16.0e9
    assert PG.latent_row_width(cfg) == 640


def test_interleaved_dense_layers_are_refused():
    with pytest.raises(NotImplementedError, match="moe_layer_freq"):
        _toy(moe_layer_freq=2)


def test_params_axes_and_count_follow_the_two_segments(toy):
    cfg, _, params = toy
    assert set(params) == {"tok_emb", "dense_blocks", "blocks",
                           "final_norm", "lm_head"}
    assert params["dense_blocks"]["w_up"].shape == (1, 64, 160)
    assert params["blocks"]["w_up"].shape == (2, 8, 64, 32)
    assert "gate_w" not in params["dense_blocks"]
    axes = T.param_logical_axes(cfg)
    is_axes = lambda t: isinstance(t, tuple)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda a: 0, axes,
                                           is_leaf=is_axes)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    n = sum(x.size for x in jax.tree.leaves(params))
    # num_params counts a bias the rmsnorm final norm does not have
    assert cfg.num_params() - n == cfg.hidden_size


def test_importer_stacks_the_dense_layer_apart():
    from deepspeed_tpu.models.hf_import import params_from_deepseek

    cfg, _, _ = _toy()
    H, E = cfg.hidden_size, cfg.n_experts
    rng = np.random.default_rng(0)
    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg.vocab_size, H)),
          "model.norm.weight": np.ones(H),
          "lm_head.weight": rng.normal(size=(cfg.vocab_size, H))}
    qout = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    kvout = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    for i in range(cfg.num_layers):
        lyr = f"model.layers.{i}."
        sd[lyr + "input_layernorm.weight"] = np.ones(H)
        sd[lyr + "post_attention_layernorm.weight"] = np.ones(H)
        att = lyr + "self_attn."
        sd[att + "q_proj.weight"] = rng.normal(size=(qout, H))
        sd[att + "kv_a_proj_with_mqa.weight"] = rng.normal(
            size=(cfg.kv_lora_rank + cfg.qk_rope_head_dim, H))
        sd[att + "kv_a_layernorm.weight"] = np.ones(cfg.kv_lora_rank)
        sd[att + "kv_b_proj.weight"] = rng.normal(
            size=(kvout, cfg.kv_lora_rank))
        sd[att + "o_proj.weight"] = rng.normal(
            size=(H, cfg.num_heads * cfg.v_head_dim))
        mlp = lyr + "mlp."
        if i < cfg.first_dense_layers:
            for w, shape in (("gate_proj", (cfg.ffn_size, H)),
                             ("up_proj", (cfg.ffn_size, H)),
                             ("down_proj", (H, cfg.ffn_size))):
                sd[mlp + w + ".weight"] = np.full(shape, float(i + 1))
            continue
        sd[mlp + "gate.weight"] = rng.normal(size=(E, H))
        sd[mlp + "gate.e_score_correction_bias"] = rng.normal(size=(E,))
        for w, shape in (("gate_proj", (cfg.moe_ffn, H)),
                         ("up_proj", (cfg.moe_ffn, H)),
                         ("down_proj", (H, cfg.moe_ffn))):
            for e in range(E):
                sd[mlp + f"experts.{e}.{w}.weight"] = np.full(
                    shape, float(10 * i + e))
            sd[mlp + f"shared_experts.{w}.weight"] = rng.normal(
                size=(shape[0] * 1, shape[1]) if w != "down_proj"
                else (H, cfg.moe_shared_size))
    params = params_from_deepseek(sd, cfg)
    want = jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.map(lambda a: a.shape, params) \
        == jax.tree.map(lambda a: a.shape, want)
    assert params["dense_blocks"]["w_up"][0, 0, 0] == 1.0
    # expert layer 0 of the stack is the checkpoint's layer 1, layer 1 its 2
    assert params["blocks"]["w_up"][0, 3, 0, 0] == 13.0
    assert params["blocks"]["w_up"][1, 3, 0, 0] == 23.0


def test_forward_matches_the_reference(toy):
    cfg, arch, params = toy
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 50)).astype(np.int32)
    want = R.forward_logits(params, toks, arch)
    got = T.forward(params, jnp.asarray(toks), cfg)
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("what", ["forward_decode", "pipeline"])
def test_other_entry_points_refuse_in_one_sentence(toy, what):
    cfg, _, params = toy
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError,
                       match="one homogeneous layer stack"):
        if what == "forward_decode":
            T.forward_decode(params, toks, None, jnp.zeros((1,), jnp.int32),
                             cfg)
        else:
            T._pipeline_parts(params, toks, cfg, None, 2, None, None, None)


def test_training_through_dst_initialize_runs(toy):
    """``dst.initialize`` + ``train_batch`` on the two-segment stack (ZeRO
    on the CPU mesh): the loss of a repeated batch falls."""
    import deepspeed_tpu as dst

    cfg, _, _ = toy
    engine, *_ = dst.initialize(
        model=dst.causal_lm_spec(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 1}})
    n = engine.train_batch_size() if hasattr(engine, "train_batch_size") \
        else 8
    batch = {"tokens": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (n, 16)).astype(np.int32)}
    losses = [float(engine.train_batch(iter([batch]))) for _ in range(4)]
    assert losses[-1] < losses[0]


# ------------------------------------------------------------------ #
# the paged tick against the reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_chunked_prefill_and_decode_match_the_reference(toy, path):
    cfg, arch, params = toy
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 60).astype(np.int32)
    want = R.forward_logits(params, toks[None], arch)[0]
    # chunked prefill of 44 positions 24 at a time, then decode steps; any
    # kernel handed in selects the latent kernel (interpreted here)
    eng = FastGenEngine(cfg, params, n_blocks=128, block_size=BS,
                        max_blocks_per_seq=MB, token_budget=32,
                        use_pallas_kernel=False)
    got = drive(eng, toks[None], paged_attention if path == "kernel" else None,
                24, 44)[0][0]
    # the kernel's products take bf16 operands
    assert rel(got, want) < (5e-3 if path == "kernel" else 1e-5)


def test_the_latent_tick_holds_the_latent_kernel_not_the_dense_one(toy):
    cfg, _, params = toy
    pool = PG.init_paged_kv(cfg, 16, BS)
    z = jnp.zeros((16,), jnp.int32)
    text = jax.jit(lambda p, pool: PG.forward_paged(
        p, z, z, jnp.zeros((16, MB), jnp.int32), pool, cfg,
        attention_fn=paged_attention)).lower(params, pool).as_text(
            debug_info=True)
    # the kernel's name of its own (interpreted here, so no Mosaic call:
    # the name is a scope of the interpreted body's operations)
    assert '"attn/latent_paged_attention/pallas_call' in text
    for scope in ("embed", "attn", "mlp", "router", "experts",
                  "shared_experts", "lm_head"):
        # inside a scan's body the name stack starts at the scope
        assert f'/{scope}/' in text or f'"{scope}/' in text, scope


def test_a_rows_logits_do_not_depend_on_its_tick_mates(toy):
    """Dropless: the same row alone (beside one other) and among 500 rows
    that crowd its experts."""
    cfg, _, params = toy
    rng = np.random.default_rng(2)
    table = np.zeros((MB,), np.int32)
    table[0] = 1

    def run(n_other):
        Tn = 512
        pool = PG.init_paged_kv(cfg, 600, BS)
        t = np.zeros((Tn,), np.int32)
        pos = np.zeros((Tn,), np.int32)
        tb = np.zeros((Tn, MB), np.int32)
        t[0], tb[0] = 7, table
        for r in range(1, 1 + n_other):
            # the others: position 0 of sequences of their own, every one
            # the row's own token so that they crowd its experts
            t[r], tb[r, 0] = 7, 1 + r
        logits, _, stats = PG.forward_paged(
            params, jnp.asarray(t), jnp.asarray(pos), jnp.asarray(tb), pool,
            cfg, with_stats=True)
        return logits[0], np.asarray(stats["expert_rows"])

    alone, rows_alone = run(1)
    crowded, rows_crowded = run(500)
    assert rel(crowded, alone) < 1e-5
    # the counts are of real rows only (pad rows route too)
    assert rows_alone.shape == (2, cfg.n_experts)
    assert rows_alone.sum(axis=1).tolist() == [2 * cfg.moe_top_k] * 2
    assert rows_crowded.sum(axis=1).tolist() == [501 * cfg.moe_top_k] * 2
    assert rows_crowded.max() == 501          # every row chose the same


def test_a_capacity_would_have_dropped_what_the_tick_keeps(toy):
    """The same crowded tick through ``moe_ffn`` with a capacity factor
    loses rows; the tick's expert layer does not use it."""
    from deepspeed_tpu.moe.layer import dropless_moe_ffn, moe_ffn

    cfg, _, params = toy
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.broadcast_to(jnp.linspace(-1, 1, cfg.hidden_size), (256, 64))
    experts = {k: lp[k] for k in ("w_up", "w_down", "w_gate")}
    kw = dict(k=cfg.moe_top_k, score_func="sigmoid", route_norm=True,
              gate_bias=lp["gate_bias"])
    y, rows = dropless_moe_ffn(x, lp["gate_w"], experts, "swiglu", **kw)
    dense, _ = moe_ffn(x[None], lp["gate_w"], experts, activation="swiglu",
                       capacity_factor=1.25, dispatch="dense", **kw)
    assert int(rows.max()) == 256
    assert rel(dense[0, 0], y[0]) < 1e-5       # the first rows fit
    assert rel(dense[0, -1], y[-1]) > 0.5      # the last were dropped


# ------------------------------------------------------------------ #
# the latent kernel, interpreted, against the jnp path
# ------------------------------------------------------------------ #
def _latent_case(lengths_and_tables, cfg, bs=BS, table_blocks=6, seed=0):
    """Random pool and queries; rows as (length, table id) pairs, table id
    0 the pad rows' all-zero table; a table names ``table_blocks`` blocks
    of ``bs`` positions."""
    rng = np.random.default_rng(seed)
    W = PG.latent_row_width(cfg)
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    n_tab = max(t for _, t in lengths_and_tables)
    NB = max(40, n_tab * table_blocks // 2)       # tables share blocks
    pool = np.zeros((NB, bs, W), np.float32)
    pool[:, :, :used] = rng.normal(size=(NB, bs, used))
    tabs = np.zeros((n_tab + 1, -(-table_blocks // 16) * 16), np.int32)
    for t in range(1, n_tab + 1):
        tabs[t, :table_blocks] = rng.permutation(
            np.arange(1, NB))[:table_blocks]
    Tn = len(lengths_and_tables)
    q = rng.normal(size=(Tn, cfg.num_heads,
                         cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    w_kv_b = rng.normal(size=(cfg.kv_lora_rank, cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim))) * 0.2
    lengths = np.array([n for n, _ in lengths_and_tables], np.int32)
    tables = np.stack([tabs[t] for _, t in lengths_and_tables])
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(tables), jnp.asarray(lengths),
            jnp.asarray(w_kv_b, jnp.bfloat16))


# the published widths, blocks of 32: 16 heads on one 640-wide row, where a
# fetch step is C = 512 positions (a toy row's step holds a toy table whole)
C = 512
LATENT_CASES = {
    # decode rows, each its own table: runs of one row
    "runs-of-one": [(n, i + 1) for i, n in enumerate(
        [1, 8, 9, 33, 40, 47, 17, 25])],
    # a chunk of 40 rows of one sequence across tiles (tiles hold 32 rows),
    # after three decode rows
    "chunk-across-two-tiles": [(30, 1), (12, 2), (44, 3)] + [
        (n, 4) for n in range(3, 43)],
    # a short chunk, then pad rows (zero table, length 1) to the bucket
    "pad-rows": [(n, 1) for n in range(5, 15)] + [(1, 0)] * 22,
    # walks that end a position before, on and after a step's edge and in a
    # third step
    "published-runs-of-one-at-the-steps-edges": [(n, i + 1) for i, n in
        enumerate([1, C - 1, C, C + 1, 2 * C + 17, 32, C + 32])],
    # a chunk crossing a tile boundary and a step's edge, its first rows in
    # one tile with three decode rows; then a run of pad rows
    "published-chunk-across-a-tile-and-a-steps-edge": [
        (C + 1, 1), (1, 2), (2 * C + 17, 3)] + [
        (n, 4) for n in range(C - 20, C + 25)] + [(1, 0)] * 16,
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_kernel_matches_the_jnp_path(toy, case):
    published = case.startswith("published")
    cfg = model_config.build(_config_file(), "serve") if published else toy[0]
    q, pool, tables, lengths, w_kv_b = _latent_case(
        LATENT_CASES[case], cfg,
        **(dict(bs=32, table_blocks=-(-(2 * C + 17) // 32)) if published
           else {}))
    if published:
        q_row = jax.ShapeDtypeStruct(
            (q.shape[0], cfg.num_heads, pool.shape[2]), pool.dtype)
        assert 32 * _geometry(q_row, (pool,), cfg.kv_lora_rank,
                              False)[3] == C
    want = PG.paged_mla_attention_reference(q, pool, tables, lengths, w_kv_b,
                                            cfg)
    # the kernel is handed the widest tier's table: the columns past a
    # walk's last block are never read
    wide = jnp.pad(tables, ((0, 0), (0, 256 - tables.shape[1])))
    got = PG._absorbed(
        q, w_kv_b, cfg, lambda q_row: latent_paged_attention(
            q_row, pool, wide, lengths, cfg.kv_lora_rank,
            PG.mla_softmax_scale(cfg), interpret=True))
    real = np.asarray(tables)[:, 0] > 0
    assert rel(got[real].astype(jnp.float32),
                want[real].astype(jnp.float32)) < 2e-2   # bf16 values
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


def test_latent_kernel_is_the_dense_kernels_walk():
    """One body, two instantiations: both entry points end in the same
    jitted ``_tiles`` over the same ``_kernel``."""
    from deepspeed_tpu.ops.pallas import paged_attention as K

    assert K.paged_attention.__code__.co_names.count("_walk") == 1
    assert K.latent_paged_attention.__code__.co_names.count("_walk") == 1
    assert tile_rows(16, 512) == 32    # 16 query heads on one 512-wide value


# ------------------------------------------------------------------ #
# the engine
# ------------------------------------------------------------------ #
def test_fastgen_serves_it_and_reports_the_experts_load(toy):
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.serving import ServingFrontend

    cfg, arch, params = toy
    eng = FastGenEngine(cfg, params, n_blocks=96, block_size=BS,
                        max_blocks_per_seq=MB, token_budget=32,
                        use_pallas_kernel=True, seed=0)
    assert eng._tile_rows == tile_rows(cfg.num_heads, cfg.kv_lora_rank)
    assert eng._expert_layers == 2 and set(eng.pool) == {"latent"}
    fe = ServingFrontend(eng)
    hist = telemetry.histogram("fastgen_expert_load_imbalance")
    def count():
        return sum(c.count for _, c in hist.labels_items())

    from deepspeed_tpu.telemetry import tracing
    tracer = tracing.get_tracer()
    was, tracer.enabled = tracer.enabled, True
    n0 = count()
    prompts = {1: list(range(3, 43)), 2: [5, 6, 7]}
    try:
        for uid, p in prompts.items():
            fe.submit(uid, p, max_new_tokens=5)
        ticks = 0
        while fe.active_count():
            fe.run_tick()
            ticks += 1
        events = tracer.export_chrome()["traceEvents"]
    finally:
        tracer.enabled = was
    assert count() - n0 == ticks
    # what a tick's attention and its experts had to do rides on its spans
    # (the roofline readers join them to the tick's device time)
    spans = {name: [e["args"] for e in events if e.get("name") == name]
             [-ticks:] for name in ("decode_tick", "tick_commit")}
    commits = {a["tick"]: a for a in spans["tick_commit"]}
    assert len(spans["decode_tick"]) == ticks == len(commits)
    for a in spans["decode_tick"]:
        assert 0 < commits[a["tick"]]["experts_active"] <= 2 * cfg.n_experts
        assert a["experts_max_rows"] >= a["experts_mean_rows"] > 0
    # the 40-token prompt alone in the first tick (budget 32): positions
    # 0..31 attend to 1..32 cache positions; the rest follows
    first = spans["decode_tick"][0]
    assert (first["prefill_tokens"], first["prompt_attended"]) \
        == (32, 32 * 33 // 2)
    assert sum(a["prompt_attended"] for a in spans["decode_tick"]) \
        == 40 * 41 // 2 + 3 * 4 // 2
    for uid, p in prompts.items():
        res = fe.result(uid)
        assert res.state == "completed" and len(res.tokens) == 5
        # greedy tokens are the reference's argmax, step by step
        seq = list(p)
        for tok in res.tokens:
            want = R.forward_logits(params, np.asarray([seq], np.int32),
                                    arch)[0, -1]
            top2 = jnp.sort(want)[-2:]
            if float(top2[1] - top2[0]) > 1e-2:     # not a near tie
                assert tok == int(jnp.argmax(want)), (uid, len(seq))
            seq.append(tok)
    assert eng.allocator.free_blocks == 96 - 1
    fe.close()


def test_span_note_reaches_the_flight_recorder():
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import tracing

    tracer = tracing.get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        with telemetry.span("decode_tick", attrs={"tick": 1}) as sp:
            sp.note(experts_max_rows=7, experts_mean_rows=1.5)
        events = [e for e in tracer.export_chrome()["traceEvents"]
                  if e.get("name") == "decode_tick"]
        assert events[-1]["args"]["experts_max_rows"] == 7
        assert events[-1]["args"]["experts_mean_rows"] == 1.5
        assert events[-1]["args"]["tick"] == 1
    finally:
        tracer.enabled = was


# ------------------------------------------------------------------ #
# the cell's tolerance catches each broken variant
# ------------------------------------------------------------------ #
def _logits_check():
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "serve-moonlight16b-longdoc-closed.json")) as f:
        return json.load(f)["logits_check"]


@pytest.fixture(scope="module")
def deep_toy():
    """Toy width at the cell's depth and routing: 1 + 8 layers, 64 experts
    top-6, 2 shared, weights as the benchmark makes them; and what the
    system's own rounding costs there (``T.forward`` in bfloat16)."""
    cfg, arch, _ = _toy(num_hidden_layers=9, n_routed_experts=64,
                        num_experts_per_tok=6, n_shared_experts=2)
    params = weights.init_on_device(cfg, 11)
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (1, 48)).astype(np.int32)
    want = R.forward_logits(params, toks, arch)[0, -9:]
    system = T.forward(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
        jnp.asarray(toks), dataclasses.replace(cfg, dtype="bfloat16"))
    return arch, params, toks, want, rel(system[0, -9:], want)


BROKEN = {
    "dropped-shared-expert": lambda arch, p: (arch, {**p, "blocks": {
        k: v for k, v in p["blocks"].items() if not k.startswith("sw_")}}),
    "top-5-for-top-6": lambda arch, p: ({**arch, "top_k": 5}, p),
    "no-routed-scaling-factor": lambda arch, p: (
        {**arch, "route_scale": 1.0}, p),
    "softmax-for-sigmoid": lambda arch, p: ({**arch, "sigmoid": False}, p),
    "dropped-dense-layer": lambda arch, p: (arch, {
        k: v for k, v in p.items() if k != "dense_blocks"}),
}


def test_the_cells_tolerance_sits_between_the_system_and_each_mistake():
    """At the published widths, on the chip (``benchmarks/tools/
    logits_probe.py``; the readings are kept in the cell's file): the
    tolerance clears the largest reading of the system by a margin and
    fails the reference in float8 and each mistake made on purpose."""
    spec = _logits_check()
    seen = spec["chip_readings"]
    assert spec["rel_tol"] >= 1.3 * seen["system_max"]
    assert spec["rel_tol"] < seen["reference_in_float8_e4m3_min"]
    assert set(seen["mistakes"]) == set(BROKEN)
    assert spec["rel_tol"] < min(seen["mistakes"].values())


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_each_broken_variant_fails_the_cells_tolerance(deep_toy, variant):
    """On the CPU at toy width every reading is smaller (an expert's output
    is a smaller share of the residual stream at hidden 64 than at 2,048),
    the system's own among them: the tolerance is carried over by its
    ratio to the system's largest reading on the chip, and each mistake
    must stand out from the toy system's rounding by that ratio."""
    arch, params, toks, want, system = deep_toy
    spec = _logits_check()
    tol = system * spec["rel_tol"] / spec["chip_readings"]["system_max"]
    arch2, params2 = BROKEN[variant](arch, params)
    got = R.forward_logits(params2, toks, arch2)[0, -9:]
    assert rel(got, want) > tol, (variant, tol)
