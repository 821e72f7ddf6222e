"""Window and full attention layers of ONE block in one stack, over a
leading dense prefix and expert layers that hold a share of their experts
(``TransformerConfig.standard_blocks`` / ``moe_router_experts``; the
``afmoe`` family, Arcee Trinity).

Toy widths, float32, matmul precision "highest": the paged tick
(``models/paged.forward_paged`` over the engine's block ranges and rings),
the whole-sequence forward (``T.forward``) and the plain reference
(``benchmarks/reference/afmoe_lm.py``, which imports nothing of the
program) are three implementations of the same equations and agree to
rounding, ~1e-6 relative; the tolerance 2e-5 leaves room for the order of
float32 sums and none for a wrong mask, ring, rotary or expert (the
smallest fault tried while writing this, rotary on the full layer, read
0.04).
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import afmoe_lm as R
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model,
                                            params_from_afmoe)
from deepspeed_tpu.moe import layer as MOE
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

_TYPES = {"w": "sliding_attention", "f": "full_attention"}


def _hf(kinds: str, dense: int, **kw):
    hf = dict(model_type="afmoe", hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, head_dim=16, num_attention_heads=6,
              num_key_value_heads=2, num_hidden_layers=len(kinds),
              layer_types=[_TYPES[k] for k in kinds], num_dense_layers=dense,
              num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
              rms_norm_eps=1e-5, rope_theta=10000, route_norm=True,
              route_scale=2.448, score_func="sigmoid", sliding_window=16,
              tie_word_embeddings=False, vocab_size=128,
              max_position_embeddings=4096, mup_enabled=True, n_group=1)
    hf.update(kw)
    return hf


#: the benchmark's cut (one dense layer, one whole period of expert layers,
#: 4 of 16 experts held) and the published pattern at toy width: a dense
#: prefix of three layers, three full layers, an expert stack that starts
#: inside a period and does not end on a period's boundary
FAMILY = H.Family(R, tokens=(2, 60), models={
    "cut": _hf("wwwwf", 1, num_experts=4, router_experts=16),
    "published": _hf("wwwfwwwfwwwfww"[:13], 3),
})
MODELS = FAMILY.models
STACKS = sorted(MODELS)


@pytest.fixture(scope="module", params=STACKS)
def model(request):
    m = FAMILY.model(request.param)
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


MISTAKES = ("no-gate", "rope-on-full", "no-window", "top-3", "no-route-scale",
            "no-shared", "no-emb-multiplier", "no-post-norms", "other-experts")


def _mistakes(cfg, params):
    """Each fault the cell's notes list, made in the program's config (or
    its parameters)."""
    return {
        "no-gate": (dict(attn_gate=False), params),
        "rope-on-full": (dict(kind_rope=()), params),
        "no-window": (dict(attn_window=4096), params),
        "top-3": (dict(moe_top_k=3), params),
        "no-route-scale": (dict(moe_route_scale=1.0), params),
        "no-shared": (dict(moe_shared_size=0), {**params, "blocks": {
            k: v for k, v in params["blocks"].items()
            if not k.startswith("sw_")}}),
        "no-emb-multiplier": (dict(emb_multiplier=1.0), params),
        "no-post-norms": (dict(post_norms=False), params),
        "other-experts": (dict(moe_first_expert=4), params),
    }


test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, STACKS)
# 60 positions under a window of 16 and a ring of 32: the ring wraps, the
# window's edge falls inside chunks, runs are longer than a block (a
# position outside a window, or a ring block not yet written, must not be
# read)
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, STACKS, n_prompt=50, cases=[
        (None, 13, TOL, {}),      # chunk and sequence boundaries fall mid-tick
        (paged_attention, 13, TOL, {}),   # the kernels (interpret mode)
        (None, 16, TOL, {}),      # a full tick: the ring holds window + run
    ])
test_a_mistake_made_on_purpose_is_seen = H.program_mistake_test(
    FAMILY, "cut", MISTAKES, _mistakes, toks_of=lambda t: t[:1, :40])


def test_segments_periods_and_pools(model):
    cfg, params, *_ = model
    kinds = cfg.layer_kinds
    d = cfg.first_dense_layers
    assert cfg.standard_blocks
    assert [(k, c.num_layers, bool(c.n_experts), c.layer_kinds)
            for k, c in cfg.segments] == [
        ("dense_blocks", d, False, kinds[:d]),
        ("blocks", len(kinds) - d, True, kinds[d:])]
    # every layer's own four norms, gate and q/k norms, stacked by segment
    for key, seg in cfg.segments:
        lp = params[key]
        assert lp["wg"].shape == (seg.num_layers, 64, 96)
        assert {"ln1", "ln1_post", "ln2", "ln2_post", "q_norm",
                "k_norm"} <= set(lp)
    assert params["blocks"]["gate_w"].shape[-1] == 16      # the router whole
    assert params["blocks"]["gate_bias"].shape[-1] == 16
    assert params["blocks"]["w_up"].shape[1] == cfg.n_experts
    # a block range for EVERY full layer, a ring for every window layer,
    # a ring of window + the longest run of one sequence's rows in a tick
    pool = PG.init_paged_kv(cfg, 40, 4, state_slots=3, max_run=16)
    assert pool["k"].shape == (kinds.count("full"), 40, 4, 2, 16)
    assert pool["wk"].shape == (kinds.count("window"), 4, 8, 4, 2, 16)
    assert PG.ring_blocks(cfg, 4, 16) * 4 == cfg.attn_window + 16
    H.assert_axes_name_every_leaf(cfg, params)
    # (num_params counts a bias on the final RMSNorm: test_latent_moe_serving)
    assert cfg.num_params() - cfg.hidden_size == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("kinds,want", [
    ("wwwf", [(0, "wwwf", 1)]),
    ("wfwwwfwwwf", [(0, "wfww", 2), (8, "wf", 1)]),
    ("wwwfww", [(0, "wwwf", 1), (4, "w", 2)]),
    ("w", [(0, "w", 1)]),
    ("wwwfwwwfwwwf", [(0, "wwwf", 3)]),
])
def test_a_run_of_kinds_is_cut_into_periods(kinds, want):
    assert [(a, "".join(p), n) for a, p, n in T.kind_runs(kinds)] == want


def test_window_layers_hold_a_ring_and_full_layers_grow(model):
    """After 3 x the ring's positions a sequence holds one slot, the full
    layers' blocks alone grew, and no other slot's ring was touched."""
    cfg, params, toks, *_ = model
    eng = H.engine(FAMILY, cfg, params, n_blocks=40, max_blocks_per_seq=32)
    n_full = cfg.layer_kinds.count("full")
    ring = 8 * 4
    eng.put([7], [toks[0, :50].tolist()])
    while eng.seqs[7].pos < 3 * ring:
        eng.step()
    seq = eng.seqs[7]
    assert len(seq.blocks) == (seq.pos - 1) // 4 + 1 and seq.blocks[0] == 1
    assert eng.allocator.free_slots == 2
    written = np.asarray(jnp.any(eng.pool["k"] != 0, axis=(2, 3, 4)))
    assert written.shape == (n_full, 40)
    for layer in range(n_full):          # every full layer, its own range
        assert set(np.flatnonzero(written[layer])) == set(seq.blocks) | {0}
    wk = np.asarray(jnp.any(eng.pool["wk"] != 0, axis=(3, 4, 5)))
    assert wk[:, 1].all() and not wk[:, 2:].any()


@pytest.mark.parametrize("name", STACKS)
def test_slots_are_freed_and_admission_waits_for_one(name):
    """Three requests on two slots: the third's first chunk waits, is
    counted once, takes the slot the first to end hands on; greedy tokens
    are the reference's; finish, flush and expiry give slot and blocks
    back."""
    m = FAMILY.model(name)
    toks = m.toks
    eng = H.engine(FAMILY, m.cfg, m.params, state_slots=2)
    prompts = {1: toks[0, :9].tolist(), 2: toks[1, :30].tolist(),
               3: toks[0, 20:37].tolist()}
    want = {1: 3, 2: 12, 3: 4}
    waits = eng._tm_slot_waits.total()
    slots_seen, _ = H.serve_greedy(eng, prompts, want)
    assert eng._tm_slot_waits.total() - waits == 1
    assert all(b in (1, 2) for b in slots_seen.values())
    assert slots_seen[3] == slots_seen[1]     # handed on by the first to end
    H.assert_greedy_tokens_are_the_reference_s(FAMILY, m, eng, prompts, want)
    eng.flush([1, 2, 3])
    assert eng.allocator.free_slots == 2 and eng.allocator.free_blocks == 63
    # flush of a live sequence, and a deadline that has passed
    eng.put([4], [toks[0, :9].tolist()])
    eng.put([5], [toks[1, :9].tolist()], deadline_s=0.0)
    eng.step()
    assert eng.expired(5) and eng.allocator.free_slots == 1
    eng.flush([4, 5])
    assert eng.allocator.free_slots == 2 and eng.allocator.free_blocks == 63


def test_failed_tick_leaves_slots_and_rings_as_they_were(model):
    cfg, params, toks, *_ = model
    eng = H.engine(FAMILY, cfg, params)
    eng.put([1], [toks[0, :20].tolist()])
    eng.step()
    eng.put([2], [toks[1, :9].tolist()])
    before = (eng.allocator.snapshot(), eng.seqs[1].pos,
              jax.tree.map(np.asarray, eng.pool))
    good = eng._ticks

    class Boom(dict):
        def __getitem__(self, key):
            raise RuntimeError("injected")

    eng._ticks = Boom(good)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    eng._ticks = good
    assert eng.allocator.snapshot() == before[0]
    assert eng.seqs[1].pos == before[1] and not eng.seqs[2].blocks
    for k, v in before[2].items():
        np.testing.assert_array_equal(np.asarray(eng.pool[k]), v)
    eng.step()
    assert eng.seqs[2].blocks[0] == 2


# --------------------------------------------------------------------- #
# the share of an expert layer
# --------------------------------------------------------------------- #

def _expert_layer(seed=0, T_=24, H=32, F=16, E=16, k=4):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)

    lp = {"gate_w": draw(H, E), "gate_bias": draw(E) * 0.2,
          "sw_gate": draw(H, F), "sw_up": draw(H, F), "sw_down": draw(F, H)}
    experts = {"w_gate": draw(E, H, F), "w_up": draw(E, H, F),
               "w_down": draw(E, F, H)}
    return draw(T_, H), lp, experts, k


def _share(x, lp, experts, k, first, held, shared=True):
    cut = {n: w[first:first + held] for n, w in experts.items()}
    return MOE.dropless_moe_ffn(
        x, lp["gate_w"], cut, "swiglu", k, score_func="sigmoid",
        route_norm=True, route_scale=2.448,
        shared={n: lp[n] for n in ("sw_gate", "sw_up", "sw_down")}
        if shared else None, gate_bias=lp["gate_bias"], first_expert=first)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4: the routed parts that all eight shares give,
    plus the shared expert counted once, are the uncut reference's expert
    layer; every share routes over all 16 experts and reports the same
    rows for them."""
    x, lp, experts, k = _expert_layer()
    arch = dict(top_k=k, route_norm=True, route_scale=2.448, first_expert=0)
    with jax.default_matmul_precision("highest"):
        want, _ = R._moe(x, lp, jax.tree.map(lambda w: w[None], experts), 0,
                         arch)
        parts = [_share(x, lp, experts, k, 2 * i, 2, shared=False)
                 for i in range(8)]
        whole, rows = _share(x, lp, experts, k, 0, 16)
        shared_once = R._mlp(x, lp["sw_gate"], lp["sw_up"], lp["sw_down"])
        # one share with the shared expert is that share's chip
        one, _ = _share(x, lp, experts, k, 6, 2)
    total = sum(p for p, _ in parts) + shared_once
    assert rel(total, want) < TOL and rel(whole, want) < TOL
    assert rel(one, parts[3][0] + shared_once) < TOL
    for _, r in parts:
        np.testing.assert_array_equal(r, rows)
    assert rows.shape == (16,) and int(rows.sum()) == 24 * k
    # the reference given the same share leaves the same experts out
    with jax.default_matmul_precision("highest"):
        ref_share, _ = R._moe(
            x, lp, {n: w[None, 6:8] for n, w in experts.items()}, 0,
            {**arch, "first_expert": 6})
    assert rel(one, ref_share) < TOL


def test_a_share_spends_no_grouped_matmul_rows_on_absent_experts():
    """Row counts, not timing: the groups handed to the grouped matmul
    hold the pairs on held experts alone, in the first rows; the pairs on
    absent experts lie behind them in no group."""
    x, lp, experts, k = _expert_layer(seed=3)
    gate = MOE._gate_indices(x, lp["gate_w"], lp["gate_bias"], k, "sigmoid",
                             True, 1, 1)
    idx = np.asarray(gate.experts)
    first, held = 4, 4
    order, inv, sizes, here = MOE.held_group_sizes(gate.experts, held, first)
    on_held = (idx >= first) & (idx < first + held)
    np.testing.assert_array_equal(here, on_held)
    np.testing.assert_array_equal(
        sizes, [(idx == e).sum() for e in range(first, first + held)])
    assert int(sizes.sum()) == int(on_held.sum()) < idx.size
    sorted_e = idx.reshape(-1)[np.asarray(order)]
    n = int(sizes.sum())
    assert ((sorted_e[:n] >= first) & (sorted_e[:n] < first + held)).all()
    assert (np.diff(sorted_e[:n]) >= 0).all()
    assert not ((sorted_e[n:] >= first) & (sorted_e[n:] < first + held)).any()
    # and the call itself is handed those groups
    seen = {}
    real = MOE.ragged_expert_ffn

    def spy(x_s, group_sizes, *a, **kw):
        seen["sizes"], seen["rows_share"] = group_sizes, kw.get("rows_share")
        return real(x_s, group_sizes, *a, **kw)

    MOE.ragged_expert_ffn = spy
    try:
        _share(x, lp, experts, k, first, held)
    finally:
        MOE.ragged_expert_ffn = real
    np.testing.assert_array_equal(seen["sizes"], sizes)
    assert seen["rows_share"] == held / 16


@pytest.mark.parametrize("shape,want", [
    ((2048, 1408, 2), (2048, 1408)),     # Moonlight: whole, as it was
    ((1408, 2048, 2), (1408, 2048)),
    ((3072, 3072, 2), (3072, 768)),      # Trinity: K whole, a part of N
    ((3072, 3072, 4), (3072, 384)),      # float32 has half the room
    ((12288, 3072, 2), None),            # K alone too long: the ladder
])
def test_serving_weight_tile_keeps_k_whole_where_vmem_has_room(shape, want):
    assert MOE._whole_k_tile(*shape) == want


def test_tick_reads_back_rows_for_held_and_for_all_experts():
    """The engine's counters and span attributes of a share: pairs on held
    experts against all pairs, held experts with rows."""
    m = FAMILY.model("cut")
    eng = H.engine(FAMILY, m.cfg, m.params)
    pairs = telemetry.counter("fastgen_expert_pairs_total")
    before = pairs.total()
    spans = []
    real = telemetry.span

    def spy(name, attrs=None, **kw):
        if name == "tick_commit":
            spans.append(attrs)
        return real(name, attrs=attrs, **kw)

    telemetry.span, orig = spy, telemetry.span
    try:
        eng.put([1], [list(range(20))])
        eng.step()
    finally:
        telemetry.span = orig
    attrs = spans[0]
    # 16 rows x 4 experts a row x 4 expert layers, of 16 experts 4 are held
    assert attrs["expert_pairs"] == 16 * 4 * 4
    assert 0 < attrs["expert_pairs_held"] < attrs["expert_pairs"]
    assert 0 < attrs["experts_active"] <= 4 * 4
    assert pairs.total() - before == attrs["expert_pairs"]
    hist = telemetry.get_registry().get("fastgen_held_expert_rows")
    assert hist is not None


# --------------------------------------------------------------------- #
# the importer
# --------------------------------------------------------------------- #

def test_importer_reads_the_published_config():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert cfg.layer_kinds == ("window", "window", "window", "full") * 15
    assert (cfg.first_dense_layers, cfg.n_experts, cfg.router_experts,
            cfg.moe_top_k, cfg.attn_window) == (6, 256, 256, 4, 4096)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.ffn_size,
            cfg.moe_ffn, cfg.moe_shared_size) == (48, 8, 128, 12288, 3072,
                                                  3072)
    assert cfg.post_norms and cfg.attn_gate and cfg.qk_norm
    assert cfg.rope_of("full") is None and not cfg.moe_router_experts
    assert cfg.emb_multiplier == pytest.approx(3072 ** 0.5)
    assert cfg.moe_route_scale == 2.448
    # 398.6 B in all (published: 400B)
    assert round((cfg.num_params() - cfg.hidden_size) / 1e9, 1) == 398.6
    # the benchmark's share: the router keeps its width
    cut = dict(row["config"], num_hidden_layers=5, num_dense_layers=1,
               layer_types=row["config"]["layer_types"][:1] * 4
               + ["full_attention"], num_experts=32, router_experts=256,
               vocab_size=25024)
    c = config_from_hf(types.SimpleNamespace(**cut))
    assert (c.n_experts, c.router_experts, c.moe_top_k) == (32, 256, 4)
    assert round((c.num_params() - c.hidden_size) / 1e9, 2) == 4.32
    bad = dict(row["config"], layer_types=row["config"]["layer_types"][:7])
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(types.SimpleNamespace(**bad))


def test_state_dict_under_the_family_s_names_imports(model):
    """A fabricated ``afmoe`` state dict (the family's tensor names, torch
    layout ``[out, in]``) gives back the tree it was made from; a share
    takes its own experts out of the checkpoint's."""
    cfg, params, toks, whole, _ = model
    full = dataclasses.replace(cfg, n_experts=cfg.router_experts,
                               moe_router_experts=0)
    if cfg.moe_router_experts:            # a checkpoint holds every expert
        params = H.noisy(H.init_params(full, jax.random.PRNGKey(2)))
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T}
    names = {"ln1": "input_layernorm", "ln1_post": "post_attention_layernorm",
             "ln2": "pre_mlp_layernorm", "ln2_post": "post_mlp_layernorm"}
    attn = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
            "wg": "gate_proj"}
    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    d = cfg.first_dense_layers
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i if i < d else i - d],
                          params["dense_blocks" if i < d else "blocks"])
        pre = f"model.layers.{i}."
        for ours, theirs in names.items():
            sd[pre + theirs + ".weight"] = lp[ours]["scale"]
        for ours, theirs in attn.items():
            sd[pre + f"self_attn.{theirs}.weight"] = lp[ours].T
        sd[pre + "self_attn.q_norm.weight"] = lp["q_norm"]
        sd[pre + "self_attn.k_norm.weight"] = lp["k_norm"]
        if i < d:
            for ours, theirs in mlp.items():
                sd[pre + f"mlp.{theirs}.weight"] = lp[ours].T
            continue
        sd[pre + "mlp.router.gate.weight"] = lp["gate_w"].T
        sd[pre + "mlp.expert_bias"] = lp["gate_bias"]
        for ours, theirs in mlp.items():
            sd[pre + f"mlp.shared_experts.{theirs}.weight"] = \
                lp["s" + ours].T
            for e in range(full.n_experts):
                sd[pre + f"mlp.experts.{e}.{theirs}.weight"] = lp[ours][e].T
    sd = {k: np.asarray(v) for k, v in sd.items()}
    got = params_from_afmoe(sd, cfg)
    if cfg.moe_router_experts:
        lo = cfg.moe_first_expert
        want = dict(params, blocks={
            k: (v[:, lo:lo + cfg.n_experts] if k in mlp else v)
            for k, v in params["blocks"].items()})
    else:
        want = params
        with jax.default_matmul_precision("highest"):
            assert rel(T.forward(got, jnp.asarray(toks), cfg), whole) < TOL
    H.assert_same_tree(want, got)
    hf = types.SimpleNamespace(**MODELS[
        "cut" if cfg.moe_router_experts else "published"])
    assert import_hf_model((sd, hf))[0] == cfg


test_entry_points_that_refuse_window_and_full_layers = H.entry_points_refuse_test(
    FAMILY, STACKS)

