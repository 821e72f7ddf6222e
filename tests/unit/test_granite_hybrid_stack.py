"""A stack of PAIRED blocks whose mixer is a Mamba-2 mixer (``mamba2``) or
grouped-query attention without rotary (``full``), every layer's second
half routed experts beside a shared gated MLP, the experts a share of the
router's, under four scalars: a multiplier on the embedding, one on what
EACH sublayer adds to the residual stream, the attention scores' factor as a
number of the config, and a divisor of the logits (the ``granitemoehybrid``
family, granite-4.0-h-small).

Toy widths (ONE group of ``B`` and ``C`` for all 8 heads, the gated norm
over the whole inner width, 10 experts a token of a router 16 wide, 4 held;
a period of ``mamba2`` and ``full`` in three runs), float32, matmul
precision "highest": the paged tick (``models/paged.forward_paged`` over the
engine's blocks and the slots' state), the whole-sequence forward
(``T.forward``) and the plain reference (``benchmarks/reference/
granite_hybrid_lm.py``, which imports nothing of the program and runs the
recurrence one row after another) are three implementations of the same
equations and agree to rounding, ~1e-6 relative; the tolerance 2e-5 leaves
room for the order of float32 sums (the chunked form sums a chunk's rows in
another order than the recurrence) and none for a wrong multiplier, factor,
divisor, gate, norm, expert, decay or state: every fault made on purpose
below reads fifteen to fifty thousand times the tolerance (the scores'
factor is ``1 / head_dim`` here as published: 1/16 for 1/128).
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import granite_hybrid_lm as R
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (_MAMBA2_TENSORS, config_from_hf,
                                            import_hf_model)
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

CONFIG = "benchmarks/configs/granite-4.0-h-small.json"
_TYPES = {"m": "mamba", "a": "attention"}


def _hf(kinds: str, **kw):
    """``kinds``: a letter a layer, ``m`` (mamba) or ``a`` (attention)."""
    hf = dict(
        model_type="granitemoehybrid", hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2,
        num_hidden_layers=len(kinds),
        layer_types=[_TYPES[k] for k in kinds],
        mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1,
        mamba_d_state=128, mamba_d_conv=4, mamba_chunk_size=8,
        mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False,
        intermediate_size=32, shared_intermediate_size=48,
        num_local_experts=4, router_experts=16, first_expert=0,
        num_experts_per_tok=10, hidden_act="silu",
        normalization_function="rmsnorm", rms_norm_eps=1e-5,
        attention_bias=False, position_embedding_type="nope",
        rope_theta=10000, rope_scaling=None, embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=16, tie_word_embeddings=True, vocab_size=128,
        max_position_embeddings=4096)
    hf.update(kw)
    return hf


#: the benchmark's cut in small (a period in three runs), a share of the
#: experts that does not start at the first, and a layer of each mixer
FAMILY = H.Family(R, {
    "cut": _hf("mmmamm"),
    "a-later-share": _hf("mam", first_expert=8),
    "a-layer-a-mixer": _hf("ma"),
})
STACKS = ["a-later-share", "cut"]


@pytest.fixture(scope="module", params=STACKS)
def model(request):
    m = FAMILY.model(request.param)
    return m.cfg, m.params, m.toks, m.arch


@pytest.fixture(scope="module")
def pair():
    m = FAMILY.model("a-layer-a-mixer")
    return m.cfg, m.params, m.toks, m.arch


def _stores_hold_the_first_pair(eng):
    assert float(jnp.abs(eng.pool["ssd"][:, 1:]).max(axis=(2, 3, 4)).min()) > 0


test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, STACKS)
# the second sequence's state is handed from ``ssd_chunk`` to ``ssd_chunk``
# and to ``ssd_step``
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, STACKS, n_prompt=30, cases=[
        (None, 13, TOL, {}),      # chunk and sequence boundaries fall mid-tick
        # the kernels (interpret mode) under the tick: ``ssd_step`` is
        # exact, the paged kernel multiplies in bfloat16 by design
        (paged_attention, 13, 2e-3, {}),
    ])
test_a_slot_handed_on_starts_from_zero = H.slot_handed_on_test(
    FAMILY, ["a-layer-a-mixer"], _stores_hold_the_first_pair)
test_a_fault_in_the_state_is_seen = H.state_fault_test(
    FAMILY, "a-layer-a-mixer", times=20, faults={
        "state-dropped-at-a-tick-boundary": "ssd",
        "conv-inputs-dropped-at-a-tick-boundary": "ssd_conv",
        "state-carried-into-the-next-sequence": H.CARRIED})
#: how far the system stands from the reference that makes the mistake, in
#: tolerances, at least (the system itself stands at 0.01 of one; the
#: scores' factor and rotary act in ONE layer of two on scores 4 times
#: flatter than ``head_dim ** -0.5`` would make them: 96 and 22; the least
#: of ten chosen experts is a quarter held: 24; a state dropped between
#: ticks reads 35, small beside the skip ``D x`` under the initialiser's
#: steps of 1e-3 to 1e-1)
SEEN = {"scores-by-head-dim": 50, "rotary-on-attention": 15,
        "top-k-less-one": 15}
test_a_mistake_made_on_purpose_is_seen = H.reference_mistake_test(
    FAMILY, "a-layer-a-mixer",
    seen=lambda mistake: SEEN.get(mistake, 100) * TOL,
    mistakes={m: {"faults": frozenset({m})} for m in R.FAULTS})
test_two_sequences_decode_in_one_tick_and_a_slot_is_handed_on = \
    H.two_sequences_test(FAMILY, "a-layer-a-mixer", both_decode=False)
test_entry_points_that_assume_one_stack_refuse_by_name = \
    H.entry_points_refuse_test(FAMILY, ["a-layer-a-mixer"])


# --------------------------------------------------------------------------- #
# the equations: the shares, the counts (both forms of the recurrence against
# the row-after-row one at ONE group: ``test_ssd_kernels.py``)
# --------------------------------------------------------------------------- #

def test_the_two_shares_add_up():
    """The two chips' shares of one layer (experts 0-7 and 8-15 of 16),
    with the shared MLP and the mixer counted once, give the reference's
    uncut layer."""
    hf = _hf("m", num_local_experts=16, router_experts=16)
    cfg, params, _ = H.build(hf)
    arch = R._Frozen(R.arch_from_config(hf, hf))
    blocks = params["blocks"]
    lp = jax.tree.map(lambda a: a[0],
                      {k: v for k, v in blocks.items() if k != "mamba2"})
    lp.update(jax.tree.map(lambda a: a[0], blocks["mamba2"]))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size))
    stack = {k: blocks[k] for k in ("w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want, _ = R._layer_jit(x[0], lp, stack, 0, arch=arch, kind="mamba2")
        # what both shares hold, counted once: the layer with every routed
        # expert's output matrix zero (the mixer's part and the shared MLP's)
        once, _ = R._layer_jit(
            x[0], lp, {**stack, "w_down": jnp.zeros_like(stack["w_down"])},
            0, arch=arch, kind="mamba2")
        total = -once
        for first in (0, 8):
            share = dataclasses.replace(cfg, n_experts=8,
                                        moe_router_experts=16,
                                        moe_first_expert=first)
            lp_i = {**lp, **{k: lp[k][first:first + 8]
                             for k in ("w_gate", "w_up", "w_down")}}
            total = total + T._block_forward(
                x, lp_i, share, None, None, T.dot_product_attention,
                "mamba2")[0][0]
    assert rel(total, want) < TOL
    assert rel(once, want) > 50 * TOL    # the routed experts do count


def test_the_published_config_counts_its_parameters():
    """The catalog's row through the importer: 32.2 B in all and 8.8 B a
    token (the model's own name, 32B-A9B), layer by layer as ISSUE 60
    counts them, and the benchmark's cut."""
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "granite-4.0-h-small")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    M, A, X = 121_464_448, 61_120_512, 9_437_184
    embed = 100_352 * 4_096 + 4_096
    assert (cfg.layer_kinds.count("mamba2"), cfg.layer_kinds.count("full")) \
        == (36, 4) and cfg.standard_blocks and not cfg.one_sublayer
    assert (cfg.emb_multiplier, cfg.residual_multiplier, cfg.attn_scale,
            cfg.logits_divisor, cfg.score_scale) \
        == (12.0, 0.22, 0.0078125, 16.0, 1 / 128)
    assert cfg.num_params() == 36 * M + 4 * A + 40 * 72 * X + embed \
        == 32_207_337_984
    assert cfg._sublayer_params(active=True) \
        == 36 * M + 4 * A + 40 * 10 * X + embed == 8_803_121_664
    file = json.load(open(CONFIG))
    run = config_from_hf(types.SimpleNamespace(
        **{k: v for k, v in file.items() if not isinstance(v, (dict, list))
           or k == "layer_types"}))
    assert run.num_params() == file["bytes"]["num_params_as_run"] \
        == 9 * M + A + 360 * X + 50_176 * 4_096 + 4_096 == 4_757_211_776
    assert (run.n_experts, run.router_experts, run.moe_first_expert,
            run.moe_top_k) == (36, 72, 0, 10)
    # the tick's scans: the shortest period the ten layers repeat with is
    # their first six (one step, its layers unrolled), then four `mamba2`
    # layers a step each (ISSUE 60 reckoned three runs: 5 + 1 + 4)
    assert T.kind_runs(run.layer_kinds) == [
        (0, ("mamba2",) * 5 + ("full",), 1), (6, ("mamba2",), 4)]
    # a slot's state and a position's keys and values, as the file says
    state, conv = HY.mamba2_state_shapes(run)
    assert 9 * (4 * int(np.prod(state)) + 2 * int(np.prod(conv))) \
        == file["bytes"]["state_bytes_a_sequence"] == 38_204_928
    assert 2 * 2 * run.kv_heads * run.head_dim \
        == file["bytes"]["kv_bytes_a_token"] == 4_096


def test_the_mixers_leaves_are_stacked_apart_and_the_experts_over_all(model):
    cfg, params, *_ = model
    kinds, blocks = cfg.layer_kinds, params["blocks"]
    assert cfg.standard_blocks and cfg.has_ln2 and not cfg.one_sublayer
    assert set(blocks) == {"ln1", "ln2", "mamba2", "attn", "gate_w", "w_gate",
                           "w_up", "w_down", "sw_gate", "sw_up", "sw_down"}
    for sub, kind in (("mamba2", "mamba2"), ("attn", "full")):
        assert {a.shape[0] for a in jax.tree.leaves(blocks[sub])} \
            == {kinds.count(kind)}
    assert blocks["w_up"].shape == (len(kinds), 4, 64, 32)
    assert blocks["gate_w"].shape == (len(kinds), 64, 16)
    assert blocks["mamba2"]["w_in"].shape[1:] == (64, 2 * 128 + 2 * 128 + 8)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    H.assert_axes_name_every_leaf(cfg, params)
    # the one table: a state store and a convolution store for the mixers
    # (ONE group: all 8 heads' 16 channels fill a tile's lanes), a block
    # range for the attention layers
    table = PG.cache_kinds(cfg)
    assert set(table) == {"full", "mamba2"}
    assert [s.cls for s in table["mamba2"].stores] == [PG.SLOT, PG.CONV]
    pool = PG.init_paged_kv(cfg, 8, 4, state_slots=3, max_run=16)
    assert pool["ssd"].shape == (kinds.count("mamba2"), 4, 1, 128, 128)
    assert pool["ssd_conv"].shape == (kinds.count("mamba2") * 3 * 4,
                                      128 + 2 * 128)


def _state_dict(cfg, params):
    """``params`` under the family's tensor names."""
    blocks = params["blocks"]
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.norm.weight": params["final_norm"]["scale"]}
    seen = {"mamba2": 0, "attn": 0}
    E, first = cfg.n_experts, cfg.moe_first_expert
    R_ = cfg.router_experts
    for layer, kind in enumerate(cfg.layer_kinds):
        pre = f"model.layers.{layer}."
        sd[pre + "input_layernorm.weight"] = blocks["ln1"]["scale"][layer]
        sd[pre + "post_attention_layernorm.weight"] = \
            blocks["ln2"]["scale"][layer]
        sub = T.mixer_of(kind)
        mp = jax.tree.map(lambda a: a[seen[sub]], blocks[sub])
        seen[sub] += 1
        if kind == "mamba2":
            at = pre + "mamba."
            for ours, (theirs, matrix) in _MAMBA2_TENSORS.items():
                sd[at + theirs] = mp[ours].T if matrix else mp[ours]
            sd[at + "conv1d.weight"] = mp["conv_w"].T[:, None]
        else:
            for x in "qkvo":
                sd[pre + f"self_attn.{x}_proj.weight"] = mp[f"w{x}"].T
        moe = pre + "block_sparse_moe."
        sd[moe + "router.layer.weight"] = blocks["gate_w"][layer].T
        # the checkpoint holds every expert of the model: the held ones in
        # their places, the others' rows anything
        fused = np.full((R_, 2 * cfg.moe_ffn, cfg.hidden_size), 7.0,
                        np.float32)
        fused[first:first + E] = np.concatenate(
            [np.swapaxes(blocks["w_gate"][layer], 1, 2),
             np.swapaxes(blocks["w_up"][layer], 1, 2)], axis=1)
        out = np.full((R_, cfg.hidden_size, cfg.moe_ffn), 7.0, np.float32)
        out[first:first + E] = np.swapaxes(blocks["w_down"][layer], 1, 2)
        sd[moe + "input_linear.weight"] = fused
        sd[moe + "output_linear.weight"] = out
        sd[pre + "shared_mlp.input_linear.weight"] = np.concatenate(
            [blocks["sw_gate"][layer].T, blocks["sw_up"][layer].T])
        sd[pre + "shared_mlp.output_linear.weight"] = \
            blocks["sw_down"][layer].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_state_dict_under_the_family_s_names_imports(model):
    cfg, params, *_ = model
    hf = next(h for h in FAMILY.models.values()
              if config_from_hf(types.SimpleNamespace(**h)) == cfg)
    got_cfg, got = import_hf_model((_state_dict(cfg, params),
                                    types.SimpleNamespace(**hf)))
    assert got_cfg == cfg
    H.assert_same_tree(params, got)


# --------------------------------------------------------------------------- #
# the four scalars
# --------------------------------------------------------------------------- #

def test_at_their_defaults_the_scalars_trace_nothing(pair):
    """A config whose scalars are 1 (the scores' factor ``head_dim **
    -0.5`` said as a number) lowers to the program of the config that does
    not name them, training's forward and the paged tick alike; and the
    training loss' three forms divide the logits alike."""
    cfg, params, toks, _ = pair
    plain = dataclasses.replace(cfg, emb_multiplier=1.0,
                                residual_multiplier=1.0, attn_scale=0.0,
                                logits_divisor=1.0)
    said = dataclasses.replace(plain, attn_scale=cfg.head_dim ** -0.5)
    assert said.score_scale == plain.score_scale

    def text(c):
        return jax.jit(lambda p, t: T.forward(p, t, c)).lower(
            params, jnp.asarray(toks)).as_text()

    assert text(said) == text(plain) != text(cfg)
    from deepspeed_tpu.sequence.tiled import tiled_lm_loss

    hidden, head, _ = T.forward_hidden(params, jnp.asarray(toks), cfg)
    t = jnp.asarray(toks)
    exact = T.causal_lm_loss(T.lm_logits(hidden, head, cfg), t)
    fused, tiled = (
        f(hidden, head, t, logits_divisor=cfg.logits_divisor)
        for f in (T.fused_lm_loss, tiled_lm_loss))
    assert abs(fused - exact) < 1e-5 and abs(tiled - exact) < 1e-5
    undivided = T.causal_lm_loss(T.head_matmul(hidden, head), t)
    assert abs(undivided - exact) > 1e-3
    g_fused = jax.grad(lambda h: T.fused_lm_loss(
        h, head, t, logits_divisor=cfg.logits_divisor))(hidden)
    g_exact = jax.grad(lambda h: T.causal_lm_loss(
        T.lm_logits(h, head, cfg), t))(hidden)
    assert rel(g_fused, g_exact) < 1e-4


def test_what_the_stack_does_not_write_is_refused_by_name(pair):
    cfg, params, toks, _ = pair
    for wrong, error, match in (
            (dict(first_dense_layers=1), NotImplementedError, "mamba2 layers"),
            (dict(norm="layernorm"), NotImplementedError, "mamba2 layers"),
            (dict(mamba2_groups=3), ValueError, "mamba2 layers need")):
        with pytest.raises(error, match=match):
            T.init_params(dataclasses.replace(cfg, **wrong),
                          jax.random.PRNGKey(0))
    # a learned choice of positions keeps its scores' factor
    sparse = dataclasses.replace(
        cfg, layer_kinds=("sparse",) * cfg.num_layers, pos_emb="rope",
        sparse_topk=4, index_heads=2, index_head_dim=8)
    with pytest.raises(NotImplementedError, match="head_dim \\*\\* -0.5"):
        T.init_params(sparse, jax.random.PRNGKey(0))
    # a homogeneous stack under the scalars: the slot cache's decode
    # applies none and says so
    dense = dataclasses.replace(cfg, layer_kinds=(), n_experts=0,
                                moe_router_experts=0)
    with pytest.raises(NotImplementedError, match="applies no multiplier"):
        T.forward_decode({}, jnp.zeros((1, 1), jnp.int32), {},
                         jnp.zeros((1,), jnp.int32), dense)
    hf = dict(FAMILY.models["a-layer-a-mixer"])
    for key, value, error in (
            ("attention_bias", True, NotImplementedError),
            ("normalization_function", "layernorm", NotImplementedError),
            ("position_embedding_type", "rope", NotImplementedError),
            ("tie_word_embeddings", False, NotImplementedError),
            ("layer_types", ["mamba", "conv"], ValueError)):
        with pytest.raises(error, match="granitemoehybrid"):
            config_from_hf(types.SimpleNamespace(**{**hf, key: value}))


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def test_a_tick_s_spans_say_which_form_took_which_rows_and_the_experts_pairs(
        monkeypatch):
    """What the benchmark's readers read of a tick of the paired stack:
    ``decode_tick`` says the recurrence's forms' rows (a mamba2 layer's:
    the readers multiply by the layers), ``tick_commit`` the pairs over
    EVERY layer (each is an expert layer)."""
    m = FAMILY.model("cut")
    cfg, toks = m.cfg, m.toks
    eng = H.engine(FAMILY, cfg, m.params)
    eng.put([1, 2, 3], [toks[0, :20].tolist(), toks[1, :5].tolist(),
                        toks[0, 7:8].tolist()])
    ticks = H.spy_on_spans(monkeypatch, "decode_tick")
    commits = H.spy_on_spans(monkeypatch, "tick_commit")
    eng.step()    # 16 rows: one chunk of the first prompt
    eng.step()    # its last 4 rows, the second prompt whole, the third
    eng.step()    # three decode rows
    assert [s["ssd_step_rows"] for s in ticks] == [0, 1, 3]
    assert [s["ssd_chunk_rows"] for s in ticks] == [16, 9, 0]
    assert [s["ssd_chunk_pieces"] for s in ticks] == [2, 3, 0]
    assert [s["ssd_state_rows"] for s in ticks] == [1, 3, 3]
    L, k = cfg.num_layers, cfg.moe_top_k
    assert [c["expert_pairs"] for c in commits] == [16 * k * L, 10 * k * L,
                                                    3 * k * L]
    assert all(0 < c["expert_pairs_held"] < c["expert_pairs"]
               and 0 < c["experts_active"] <= L * cfg.n_experts
               for c in commits)
    from deepspeed_tpu import telemetry

    n = cfg.layer_kinds.count("mamba2")
    per_slot = telemetry.gauge("fastgen_state_bytes_per_slot")
    assert per_slot.value(kind="ssd") == n * 8 * 16 * 128 * 4
    eng.flush([1, 2, 3])


def test_the_tick_s_operations_lie_under_the_scopes_the_readers_sort_by():
    """The paired tick: a Mamba-2 layer under ``ssd`` with the one-row
    form's call under ``ssd_step`` and the chunked form under
    ``ssd_chunk``, the attention layer's ``attn/global`` with its kernel
    ``global_attention``, and EVERY layer's ``router`` / ``experts`` /
    ``shared_experts``: what ``ssd_share_pct``, ``ssd_chunk_roofline``,
    ``global_attention_share_pct`` and ``experts_share_pct`` read."""
    import re

    from deepspeed_tpu.inference.fastgen import FastGenEngine

    cfg = FAMILY.model("a-layer-a-mixer").cfg
    eng = FastGenEngine(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=8,
                        token_budget=32, state_slots=2, seed=0,
                        use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    text = eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))
    ).compile().as_text()
    stacks = {n for n in re.findall(r'op_name="([^"]+)"', text)
              if n.startswith("jit(tick)/")}
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "ssd", "ssd_step", "ssd_chunk", "attn", "global",
            "router", "experts", "shared_experts", "lm_head",
            "sample"} <= parts
    assert any("/ssd/ssd_step/ssd_step" in s for s in stacks)
    assert any("/ssd/ssd_chunk/" in s for s in stacks)
    assert any("/attn/global/global_attention" in s for s in stacks)
    # a mixer's operations are not under another's scope, and the experts'
    # are under neither
    assert not any("/ssd/" in s and "/attn/" in s for s in stacks)
    assert not any("/experts/" in s and ("/ssd/" in s or "/attn/" in s)
                   for s in stacks)
