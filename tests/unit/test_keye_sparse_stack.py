"""Grouped-query layers that attend to the positions a learned indexer
chooses (``TransformerConfig.layer_kinds`` of ``sparse``; the ``KeyeVL2``
family's language model, DeepSeek sparse attention on the Qwen3-MoE block),
over expert layers that hold a share of their experts.

Toy widths, float32, matmul precision "highest", ``topk`` SHORTER than the
prompts: the paged tick (``models/paged.forward_paged`` over the engine's
block ranges: keys, values and index keys), the whole-sequence forward
(``T.forward``) and the plain reference
(``benchmarks/reference/keye_sparse_lm.py``, which imports nothing of the
program) are three implementations of the same equations and agree to
rounding, ~1e-6 relative. The choice is made three ways (``lax.top_k`` and a
scatter in ``T.forward``, ``lax.top_k`` a block of rows in the reference, a
bisection over the scores' bits in the tick) and every one is exact, so the
sets agree unless two scores differ by rounding alone: the tests that hold
the choice FIXED hand the reference's sets to both sides, so that a fault
in the attention cannot hide behind an exchanged position and an exchanged
position cannot pass for a fault.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import keye_sparse_lm as R
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model,
                                            params_from_keye_vl2)
from deepspeed_tpu.moe import layer as MOE
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

TOPK = 16


def _hf(**kw):
    hf = dict(model_type="KeyeVL2", hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=3, num_experts=4,
              num_local_experts=4, router_experts=8, num_experts_per_tok=2,
              norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000000,
              rope_scaling={"mrope_section": [2, 3, 3],
                            "rope_type": "default", "type": "default"},
              sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                         "q_chunk_size": 512, "topk": TOPK},
              decoder_sparse_step=1, mlp_only_layers=[], attention_bias=False,
              use_sliding_window=False, sliding_window=None,
              tie_word_embeddings=False, vocab_size=128,
              max_position_embeddings=4096)
    hf.update(kw)
    return hf


# differs: the indexer's and the router's leaves are spread widely, by name
def _noisy(params, seed=1):
    """Every leaf off its start, the indexer's and the router's widely (so
    that scores are spread and a dropped term shows)."""
    def one(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(jax.random.PRNGKey(seed),
                               hash(name) % (2 ** 31))
        with H.drawn_whole():
            noise = jax.random.normal(k, x.shape)
        if "norm" in name or "ln" in name:
            return x + 0.1 * noise
        if "gate_w" in name:
            # ``init_params`` draws a share's router as one of EQUAL shares
            # (the held experts' columns repeated): a column of its own
            # again, so that another share's columns are another result
            return 10.0 * (x + 0.02 * noise)
        return x * (10.0 if "idx" in name else 3.0)

    return jax.tree_util.tree_map_with_path(one, params)


#: three layers, and one (a set a layer can be handed to one layer alone)
FAMILY = H.Family(R, {"model": _hf(), "one-layer": _hf(num_hidden_layers=1)},
                  tokens=(2, 60), noise=_noisy,
                  engine_kw={"block_size": 8, "token_budget": 32})
assert FAMILY.tokens[1] > 3 * TOPK        # ``topk`` SHORTER than the prompts


@pytest.fixture(scope="module")
def model():
    m = FAMILY.model("model")
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


# ------------------------------------------------------------------ #
# three implementations of the same equations
# ------------------------------------------------------------------ #
test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, ["model"])
# chunked prefill (the first chunk of 27 rows crosses ``topk`` 16: its first
# rows attend to all they have, its last choose) and decode through the
# engine's pool
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, ["model"], n_prompt=48, garbage=None, free_slots=None, cases=[
        (None, 27, TOL, {}),        # chunks that cross ``topk`` and a sequence
        (None, 32, TOL, {}),
        # the kernels, interpreted: every row walks its sequence under the
        # choice as a mask. Tables of 16 blocks reach 8 x ``topk``, of 32
        # blocks 16 x; a tick of 64 rows is two of the kernels' row tiles
        (paged_attention, 27, TOL, {"use_pallas_kernel": True}),
        (paged_attention, 27, TOL, {"use_pallas_kernel": True,
                                    "max_blocks_per_seq": 32}),
        (paged_attention, 50, TOL, {"use_pallas_kernel": True,
                                    "token_budget": 64,
                                    "max_blocks_per_seq": 32}),
    ])
# each mistake of the cell's ``chip_readings``, in float32 where rounding
# hides nothing: the system stands ~1e-6 from the reference and far from the
# reference that makes the mistake (the smallest, the index key's LayerNorm
# dropped, moves the chosen sets of a toy model by a few positions a row)
test_a_mistake_made_on_purpose_is_seen = H.reference_mistake_test(
    FAMILY, "model", seen=lambda mistake: 0.02, and_the_right_one=True,
    mistakes={m: {"faults": (m,)} for m in R.FAULTS})


@pytest.mark.parametrize("kernels", [False, True])
def test_with_the_choice_held_fixed(monkeypatch, kernels):
    """The reference's own sets handed to all three: what is left to
    compare is the attention over a given set. One layer: a stack's layers
    are one traced step of a scan, which a set a layer cannot be handed
    to."""
    m = FAMILY.model("one-layer")
    cfg, params, arch, one = m.cfg, m.params, m.arch, m.toks[:1]
    sets = []
    want = R.forward_logits(params, one, arch, chosen=sets)
    assert rel(R.forward_logits(params, one, arch, given=sets), want) < 1e-6
    (given,) = sets
    # a set of the reference has exactly ``min(t + 1, topk)`` positions
    np.testing.assert_array_equal(
        np.asarray(given).sum(1), np.minimum(np.arange(60) + 1, TOPK))
    # ... and moving it moves the logits: the sets are what is compared
    moved = [jnp.roll(given, 1, axis=1) | jnp.eye(60, dtype=bool)]
    assert rel(R.forward_logits(params, one, arch, given=moved), want) > 0.01
    monkeypatch.setattr(T, "chosen_positions",
                        lambda scores, topk: given[None])
    with jax.default_matmul_precision("highest"):
        whole = T.forward(params, jnp.asarray(one), cfg)
    assert rel(whole, want) < TOL

    def fixed(scores, pos, lengths, topk, axes, reach):
        rows = jnp.pad(given, ((0, 0), (0, reach - 60)))[
            jnp.clip(lengths.reshape(-1) - 1, 0, 59)]            # [T, reach]
        if len(axes) == 2:                  # the kernels' [S / 128, T, 128]
            rows = rows.reshape(rows.shape[0], -1, 128).transpose(1, 0, 2)
        return rows & (pos < lengths)

    monkeypatch.setattr(PG, "sparse_choice", fixed)
    eng = H.engine(FAMILY, cfg, params, use_pallas_kernel=kernels)
    got, _ = H.drive(eng, one, paged_attention if kernels else None, 27, 48,
                     patched="the-choice-held-fixed")
    assert rel(got, want) < TOL


def test_contexts_no_longer_than_topk_equal_the_dense_kind(model):
    """While a row has no more than ``topk`` positions it attends to all of
    them: the ``sparse`` kind is then the ``full`` kind to the bit, in the
    whole forward and through the pool."""
    cfg, params, toks, _, _ = model
    short = toks[:, :TOPK]
    dense = dataclasses.replace(cfg, layer_kinds=("full",) * cfg.num_layers)
    a = T.forward(params, jnp.asarray(short), cfg)
    b = T.forward(params, jnp.asarray(short), dense)
    assert bool(jnp.all(a == b))
    got = H.drive(H.engine(FAMILY, cfg, params), short, None, 11, 12)[0]
    want = H.drive(H.engine(FAMILY, dense, params), short, None, 11, 12)[0]
    assert bool(jnp.all(got == want))

def test_equal_scores_choose_the_lower_position_in_the_whole_forward():
    scores = jnp.zeros((1, 40, 40), jnp.float32)
    chosen = np.asarray(T.chosen_positions(scores, 8))[0]
    for t in range(40):
        np.testing.assert_array_equal(
            np.flatnonzero(chosen[t]), np.arange(min(t + 1, 8)))


def test_the_span_counts_the_tiles_that_count():
    """``sparse_choice_tiles`` / ``_counting``: tiles of the choice's
    kernel that hold a real row, and those of them with a row over
    ``topk``: a prompt's first chunk behind three decode rows (the first
    tile counts for them, the second has no row over 64, the third has),
    and a decode tick's one tile of a bucket of eight."""
    lengths = np.concatenate([[380, 370, 375], np.arange(1, 94)])
    mixed = PG._sparse_span(6, 64, 3, [3], 96, 2048, lengths)
    assert mixed["sparse_choice_tiles"] == 6 * 3
    assert mixed["sparse_choice_tiles_counting"] == 6 * 2
    assert mixed["sparse_rows_choosing"] == 3 + 93 - 64
    decode = PG._sparse_span(6, 64, 3, [], 3, 256, lengths[:3])
    assert decode["sparse_choice_tiles"] == 6 \
        == decode["sparse_choice_tiles_counting"]
    short = PG._sparse_span(6, 64, 0, [0], 40, 256, np.arange(1, 41))
    assert short["sparse_choice_tiles"] == 12
    assert short["sparse_choice_tiles_counting"] == 0

# ------------------------------------------------------------------ #
# the pool: three stores of a layer's block range
# ------------------------------------------------------------------ #
def test_every_position_writes_its_index_key(model):
    """Rows too short to choose write their index keys all the same: a
    sequence served in one-row ticks from position 0 chooses from keys
    written long before it could choose."""
    cfg, params, toks, whole, _ = model
    eng = H.engine(FAMILY, cfg, params)
    got = H.drive(eng, toks[:1, :40], None, 32, 0)[0]
    assert rel(got, whole[:1, :40]) < TOL
    # blocks alone: a sparse layer keeps nothing a sequence slot
    assert set(eng.pool) == {"k", "v", "idx"}
    assert eng.pool["idx"].shape == (3, 64, 8, PG.index_row_width(cfg))
    # a position's key lies in its 8 columns of a padded row
    idx = np.asarray(eng.pool["idx"])
    assert np.abs(idx[..., :8]).sum() > 0 and not idx[..., 8:].any()


@pytest.mark.parametrize("kernels", [False, True])
def test_a_tick_counts_its_own_sequences(model, kernels):
    """A pool of blocks alone says nothing of slots: a tick numbers the
    sequences it holds, whatever their first blocks' ids, and a table has
    a row a sequence of the tick (``PG.tick_tables``), not a block of the
    pool. Decode rows of two sequences whose first blocks lie past any
    table's rows, the second sequence's first."""
    cfg, params, toks, whole, _ = model
    eng = H.engine(FAMILY, cfg, params, use_pallas_kernel=kernels,
                   state_slots=30,                  token_budget=4)
    for _ in range(20):                 # first blocks 21 and 22
        eng.allocator.allocate(1)
    attn = paged_attention if kernels else None
    got = H.drive(eng, toks[::-1, :20], attn, 4, 16)[0]
    assert rel(got, whole[::-1, :20]) < TOL
    # (a table's row is padded to whole lane tiles: 576 entries take 640)
    assert PG.tick_tables(4, 16) == 4 and PG.tick_tables(2048, 576) == 51


def test_the_engine_holds_its_slots_under_a_ticks_tables(model, monkeypatch):
    """Scalar memory's room for tables bounds a tick's sequences only
    where it is under a table a row."""
    cfg, params = model[:2]
    monkeypatch.setattr(PG, "TABLE_WORDS", 128 * 8)
    with pytest.raises(ValueError, match="holds 8 sequences"):
        H.engine(FAMILY, cfg, params, state_slots=8)
    H.engine(FAMILY, cfg, params, state_slots=7)
    H.engine(FAMILY, cfg, params, state_slots=8, token_budget=8)


def test_a_slots_next_sequence_sees_none_of_the_last_ones_index_keys(model):
    """Two sequences one after the other through the same blocks of the
    same slot: the second's logits are those of a fresh pool."""
    cfg, params, toks, whole, _ = model
    eng = H.engine(FAMILY, cfg, params)
    H.drive(eng, toks[:1], None, 27, 48)
    kept = np.asarray(eng.pool["idx"]).copy()
    got = H.drive(eng, toks[1:, :50], None, 27, 40)[0]
    assert np.abs(kept).sum() > 0                   # the blocks were dirty
    assert rel(got, whole[1:, :50]) < TOL


def test_engine_serves_greedy_tokens_of_the_whole_forward(monkeypatch):
    """Through ``FastGenEngine.step``: chunked prefill and decode ticks in
    one queue, every greedy token the reference's, and the span's account
    of what the sparse layers did."""
    m = FAMILY.model("model")
    eng = H.engine(FAMILY, m.cfg, m.params)
    prompts = {1: m.toks[0, :40].tolist(), 2: m.toks[1, :21].tolist()}
    want = {1: 6, 2: 6}
    scored = telemetry.counter("fastgen_index_positions_total")
    read = telemetry.counter("fastgen_sparse_positions_read_total")
    before = (scored.value(), read.value())
    spans = H.spy_on_spans(monkeypatch, "decode_tick")
    H.serve_greedy(eng, prompts, want, ticks=50)
    H.assert_greedy_tokens_are_the_reference_s(FAMILY, m, eng, prompts, want)
    first = spans[0]
    # the first tick: 32 rows of the 40-token prompt, positions 0 .. 31
    assert first["sparse_layers"] == 3 and first["rows"] == 32
    assert first["index_positions"] == 3 * sum(range(1, 33))
    assert first["index_walk_positions"] == 3 * 32
    assert first["sparse_selected"] == 3 * sum(
        min(n, TOPK) for n in range(1, 33))
    assert first["sparse_rows_choosing"] == 32 - TOPK
    # one tile of rows a layer, and a row of it is longer than ``topk``
    assert first["sparse_choice_tiles"] == 3 \
        == first["sparse_choice_tiles_counting"]
    assert first["sparse_selected_decode"] == 0
    # every row walks its sequence under the choice as a mask
    assert first["sparse_positions_read"] == first["index_positions"]
    # a decode tick of both sequences: a walk a row
    last = spans[-1]
    assert last["decode_rows"] == 2 == last["rows"]
    assert last["index_walk_positions"] == last["index_positions"]
    assert last["sparse_selected_decode"] == last["sparse_selected"] \
        == 3 * 2 * TOPK
    assert scored.value() - before[0] == sum(
        s["index_positions"] for s in spans)
    assert read.value() - before[1] == sum(
        s["sparse_positions_read"] for s in spans)
    eng.flush(list(prompts))
    assert eng.allocator.free_blocks == 63


# ------------------------------------------------------------------ #
# the experts' share
# ------------------------------------------------------------------ #
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 under a softmax router with ``norm_topk_prob``:
    the routed parts that all eight shares give are the uncut reference's
    expert layer; the reference given a share leaves the same experts
    out."""
    rng = np.random.default_rng(0)
    H, F, E, k, rows = 32, 16, 16, 4, 24

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

    lp = {"gate_w": draw(H, E)}
    experts = {"w_gate": draw(E, H, F), "w_up": draw(E, H, F),
               "w_down": draw(E, F, H)}
    x = draw(rows, H)
    arch = dict(top_k=k, route_norm=True, first_expert=0, faults=())

    def share(first, held):
        return MOE.dropless_moe_ffn(
            x, lp["gate_w"], {n: w[first:first + held]
                              for n, w in experts.items()},
            "swiglu", k, score_func="softmax", route_norm=True,
            first_expert=first)

    with jax.default_matmul_precision("highest"):
        want = R._moe(x, lp, jax.tree.map(lambda w: w[None], experts), 0,
                      arch)
        parts = [share(2 * i, 2) for i in range(8)]
        whole, counts = share(0, 16)
        ref_share = R._moe(x, lp, {n: w[None, 6:8]
                                   for n, w in experts.items()}, 0,
                           {**arch, "first_expert": 6})
    assert rel(sum(p for p, _ in parts), want) < TOL
    assert rel(whole, want) < TOL
    assert rel(parts[3][0], ref_share) < TOL
    for _, r in parts:
        np.testing.assert_array_equal(r, counts)
    assert counts.shape == (16,) and int(counts.sum()) == rows * k


# ------------------------------------------------------------------ #
# the importer
# ------------------------------------------------------------------ #
def test_importer_reads_the_published_config():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert cfg.layer_kinds == ("sparse",) * 48 and cfg.standard_blocks
    assert (cfg.sparse_topk, cfg.index_heads, cfg.index_head_dim) == (
        2048, 16, 64)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.moe_ffn,
            cfg.n_experts, cfg.router_experts, cfg.moe_top_k) == (
                32, 4, 128, 768, 128, 128, 8)
    assert cfg.qk_norm and cfg.moe_route_norm and not cfg.moe_shared_size
    assert cfg.rope_theta == 1e7 and cfg.rope_scaling is None
    assert cfg.moe_score_func == "softmax" and not cfg.moe_router_experts
    # 30.6 B in all (published: 30B)
    assert round(cfg.num_params() / 1e9, 1) == 30.6
    cut = dict(row["config"], num_hidden_layers=6, num_experts=16,
               num_local_experts=16, router_experts=128, vocab_size=18992)
    c = config_from_hf(types.SimpleNamespace(**cut))
    assert (c.n_experts, c.router_experts, c.moe_first_expert) == (16, 128, 0)
    assert round(c.num_params() / 1e6) == 659
    # the indexer a layer: 2.26 M
    per_layer = (c.num_params() - dataclasses.replace(
        c, layer_kinds=(), index_heads=0).num_params()) // 6
    assert per_layer == 2048 * 16 * 64 + 2048 * 64 + 2 * 64 + 2048 * 16
    for bad in (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
                dict(use_sliding_window=True),
                dict(sa_config=dict(row["config"]["sa_config"],
                                    indexer_num_kv_heads=2))):
        with pytest.raises(NotImplementedError):
            config_from_hf(types.SimpleNamespace(**{**row["config"], **bad}))


def test_state_dict_under_the_family_s_names_imports(model):
    """A fabricated ``KeyeVL2`` state dict (the Qwen3-MoE names under the
    language model's prefix, torch layout ``[out, in]``, beside a vision
    tower's tensors) gives back the tree it was made from; a share takes
    its own experts."""
    cfg, params, toks, whole, _ = model
    hf = _hf(num_experts=8, num_local_experts=8, router_experts=8)
    full = config_from_hf(types.SimpleNamespace(**hf))
    tree = _noisy(H.init_params(full, jax.random.PRNGKey(2)), seed=4)
    b = jax.tree.map(np.asarray, tree["blocks"])
    pre = "model.language_model."
    sd = {pre + "embed_tokens.weight": np.asarray(tree["tok_emb"]),
          pre + "norm.weight": np.asarray(tree["final_norm"]["scale"]),
          "lm_head.weight": np.asarray(tree["lm_head"]).T,
          "model.visual.patch_embed.proj.weight": np.zeros((4, 4))}
    names = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
             "gate_w": "mlp.gate", "idx_wq": "self_attn.indexer.wq",
             "idx_wk": "self_attn.indexer.wk",
             "idx_ww": "self_attn.indexer.weights_proj"}
    for i in range(3):
        lyr = f"{pre}layers.{i}."
        for ours, theirs in names.items():
            sd[lyr + theirs + ".weight"] = b[ours][i].T
        sd[lyr + "input_layernorm.weight"] = b["ln1"]["scale"][i]
        sd[lyr + "post_attention_layernorm.weight"] = b["ln2"]["scale"][i]
        sd[lyr + "self_attn.q_norm.weight"] = b["q_norm"][i]
        sd[lyr + "self_attn.k_norm.weight"] = b["k_norm"][i]
        sd[lyr + "self_attn.indexer.k_norm.weight"] = \
            b["idx_k_norm"]["scale"][i]
        sd[lyr + "self_attn.indexer.k_norm.bias"] = b["idx_k_norm"]["bias"][i]
        for e in range(8):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                sd[f"{lyr}mlp.experts.{e}.{theirs}.weight"] = b[ours][i, e].T
    got_cfg, got = import_hf_model((sd, types.SimpleNamespace(**hf)))
    assert got_cfg == full
    flat_a, flat_b = (dict(jax.tree_util.tree_leaves_with_path(t))
                      for t in (got, tree))
    assert flat_a.keys() == flat_b.keys()
    for key in flat_a:
        np.testing.assert_array_equal(flat_a[key], np.asarray(flat_b[key]))
    share_hf = _hf(num_experts=2, num_local_experts=2, router_experts=8,
                   first_expert=4)
    share_cfg = config_from_hf(types.SimpleNamespace(**share_hf))
    share = params_from_keye_vl2(sd, share_cfg)
    np.testing.assert_array_equal(share["blocks"]["w_up"], b["w_up"][:, 4:6])
    assert share["blocks"]["gate_w"].shape == (3, 64, 8)


@pytest.mark.parametrize("entry", ["forward_decode", "pipeline"])
def test_entry_points_that_refuse_sparse_layers(model, entry):
    cfg, params, toks, _, _ = model
    with pytest.raises(NotImplementedError, match="layer kinds"):
        T._require_one_stack(cfg, entry)


def test_sparse_layers_stand_alone_in_their_stack():
    hf = _hf()
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    for bad in (dict(layer_kinds=("sparse", "full", "sparse")),
                dict(sparse_topk=0), dict(pos_emb="none")):
        with pytest.raises(NotImplementedError, match="sparse layers"):
            T.init_params(dataclasses.replace(cfg, **bad),
                          jax.random.PRNGKey(0))


