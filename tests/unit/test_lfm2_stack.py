"""Gated short-convolution layers among attention layers of ONE stack of
standard blocks, over a leading dense prefix and expert layers
(``TransformerConfig.standard_blocks`` with ``conv`` layers; the
``lfm2_moe`` family, Liquid LFM2-MoE).

Toy widths, float32, matmul precision "highest": the paged tick
(``models/paged.forward_paged`` over the engine's block ranges and the
convolutions' state rows), the whole-sequence forward (``T.forward``) and
the plain reference (``benchmarks/reference/lfm2_lm.py``, which imports
nothing of the program) are three implementations of the same equations
and agree to rounding, ~1e-6 relative; the tolerance 2e-5 leaves room for
the order of float32 sums and none for a wrong tap, state, norm, rotary or
expert: every fault made on purpose below reads over a hundred times the
tolerance, but for one, the ``+ 1e-6`` of the router's division left
out, which no comparison of logits above rounding can see and which has a
test of its own on scores small enough to show it.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import lfm2_lm as R
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model,
                                            params_from_lfm2_moe)
from deepspeed_tpu.moe.gating import topk_gating, topk_gating_indices
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

_TYPES = {"c": "conv", "f": "full_attention"}


def _hf(kinds: str, dense: int, **kw):
    hf = dict(model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=len(kinds),
              layer_types=[_TYPES[k] for k in kinds], num_dense_layers=dense,
              num_experts=8, num_experts_per_tok=4, norm_eps=1e-5,
              norm_topk_prob=True, routed_scaling_factor=1,
              use_expert_bias=True, conv_L_cache=3, conv_bias=False,
              rope_parameters={"rope_theta": 1000000,
                               "rope_type": "default"},
              vocab_size=128, max_position_embeddings=4096)
    hf.update(kw)
    return hf


#: the benchmark's cut (two dense conv layers, two whole periods of expert
#: layers), the published pattern cut where a period is not whole (a period
#: of four with a remainder of three), and heads of 64, which lie two to a
#: pool row (``paged.kv_lane_pack``)
FAMILY = H.Family(R, {
    "cut": _hf("ccfcccfccc", 2),
    "remainder": _hf("ccfcccfcccfcc", 2),
    "heads-of-64": _hf("ccfccc", 2, hidden_size=128, num_attention_heads=2,
                       num_key_value_heads=2),
})
MODELS = FAMILY.models
STACKS = sorted(MODELS)


@pytest.fixture(scope="module", params=STACKS)
def model(request):
    m = FAMILY.model(request.param)
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


@pytest.fixture(scope="module")
def cut():
    m = FAMILY.model("cut")
    return m.cfg, m.params, m.toks, m.arch


def _stores_hold_the_first_pair(eng):
    # rows (layer, input, slot): both slots hold the first pair's state,
    # the pad rows' slot 0 was never written
    by_slot = eng.pool["conv"].reshape(-1, 3, eng.pool["conv"].shape[-1])
    assert float(jnp.abs(by_slot[:, 1:]).min()) > 0
    assert float(jnp.abs(by_slot[:, 0]).max()) == 0


MISTAKES = ("no-qk-norm", "no-rope", "no-expert-bias", "taps-reversed",
            "top-3", "conv-for-attention")


def _mistakes(cfg, params):
    """Each fault the cell's notes list, made in the program's config (or
    its parameters)."""
    blocks = params["blocks"]
    flip = lambda b: {**b, "conv": {  # noqa: E731
        **b["conv"], "conv_w": b["conv"]["conv_w"][:, ::-1]}}
    return dict(zip(MISTAKES, (
        (dict(qk_norm=False), params),
        (dict(pos_emb="none"), params),
        (dict(moe_gate_bias=False), {**params, "blocks": {
            k: v for k, v in blocks.items() if k != "gate_bias"}}),
        ({}, {**params, "blocks": flip(blocks),
              "dense_blocks": flip(params["dense_blocks"])}),
        (dict(moe_top_k=3), params),
        (dict(layer_kinds=("conv", "conv", "conv", "full")
              + cfg.layer_kinds[4:]), {**params, "blocks": {
                  **blocks, "conv": jax.tree.map(
                      lambda a: a[jnp.asarray([1, 0, 2, 3, 4, 5])],
                      blocks["conv"])}}))))


test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, STACKS)
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, STACKS, n_prompt=30, cases=[
        (None, 13, TOL, {}),      # chunk and sequence boundaries fall mid-tick
        (paged_attention, 13, TOL, {}),   # the kernel (interpret mode)
        (None, 16, TOL, {}),      # a full tick
    ])
test_a_slot_handed_on_starts_from_zero = H.slot_handed_on_test(
    FAMILY, STACKS, _stores_hold_the_first_pair)
test_a_fault_in_the_state_is_seen = H.state_fault_test(
    FAMILY, "cut", times=1000, faults={
        "state-dropped-at-a-tick-boundary": "conv",
        "state-carried-into-the-next-sequence": H.CARRIED})
test_a_mistake_made_on_purpose_is_seen = H.program_mistake_test(
    FAMILY, "cut", MISTAKES, _mistakes)
test_two_sequences_decode_in_one_tick_and_a_slot_is_handed_on = \
    H.two_sequences_test(FAMILY, "cut")


def test_segments_mixers_and_pools(model):
    cfg, params, *_ = model
    kinds, d = cfg.layer_kinds, cfg.first_dense_layers
    h = cfg.hidden_size
    assert cfg.standard_blocks and d == 2 and kinds[:2] == ("conv", "conv")
    assert [(k, c.num_layers, bool(c.n_experts), c.layer_kinds)
            for k, c in cfg.segments] == [
        ("dense_blocks", d, False, kinds[:d]),
        ("blocks", len(kinds) - d, True, kinds[d:])]
    # the mixers' leaves stacked by mixer, everything else by layer
    for key, seg in cfg.segments:
        lp, n_conv = params[key], seg.layer_kinds.count("conv")
        assert lp["ln1"]["scale"].shape == (seg.num_layers, h)
        assert lp["conv"]["w_in"].shape == (n_conv, h, 3 * h)
        assert lp["conv"]["conv_w"].shape == (n_conv, 3, h)
        assert lp["conv"]["wo"].shape == (n_conv, h, h)
        assert seg.mixer_layers == {
            m: n for m, n in (("attn", seg.num_layers - n_conv),
                              ("conv", n_conv)) if n}
    assert "attn" not in params["dense_blocks"]       # both layers conv
    attn = params["blocks"]["attn"]
    n_full = kinds.count("full")
    assert attn["wq"].shape == (n_full, h, h) and "wq" not in params["blocks"]
    assert attn["q_norm"].shape == (n_full, cfg.head_dim)
    assert params["blocks"]["gate_bias"].shape == (len(kinds) - d, 8)
    assert "lm_head" not in params                    # the head is tied
    # a block range for EVERY attention layer, two inputs a channel a
    # conv layer a sequence slot; heads of 64 two to a row
    pool = PG.init_paged_kv(cfg, 40, 4, state_slots=3, max_run=16)
    pack = PG.kv_lane_pack(cfg)
    assert pack == (2 if cfg.head_dim == 64 else 1)
    assert pool["k"].shape == (n_full, 40, 4, cfg.kv_heads // pack,
                               pack * cfg.head_dim)
    # a row of the store is ONE stored input of one slot (inputs-major)
    assert pool["conv"].shape == (kinds.count("conv") * 2 * 4, h)
    assert pool["conv"].nbytes // 4 == kinds.count("conv") * 2 * h * \
        pool["conv"].dtype.itemsize
    assert set(pool) == {"k", "v", "conv"}
    H.assert_axes_name_every_leaf(cfg, params)
    # (num_params counts a bias on the final RMSNorm: test_latent_moe_serving)
    assert cfg.num_params() - h == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("kinds,want", [
    ("fcccfccc", [(0, "fccc", 2)]),
    ("fcccfcccfcc", [(0, "fccc", 2), (8, "fcc", 1)]),
    ("cc", [(0, "c", 2)]),
    ("fccf", [(0, "fcc", 1), (3, "f", 1)]),
])
def test_a_run_of_kinds_is_cut_into_periods(kinds, want):
    """A period of four with a remainder; a run with no layer of one mixer
    (its steps then take no leaf of it)."""
    assert [(a, "".join(p), n) for a, p, n in T.kind_runs(kinds)] == want
    names = tuple(_TYPES[k].split("_")[0] for k in kinds)
    blocks = {"ln": jnp.arange(len(kinds)),
              **{m: {"w": jnp.arange(n)} for m, n in (
                  ("attn", kinds.count("f")), ("conv", kinds.count("c")))
                 if n}}
    seen = []

    def body_of(period, first):
        def body(carry, lps):
            for i in range(len(period)):
                lp = T.period_layer(lps, period, i)
                carry = carry + 1
                seen.append((period[i], lp["ln"], lp["w"]))
            return carry, None
        return body

    with jax.disable_jit():
        T.scan_periods(body_of, 0, blocks, names)
    assert [int(s[1]) for s in seen] == list(range(len(kinds)))
    nth = {"full": 0, "conv": 0}
    for kind, _, w in seen:       # each layer met its own mixer's leaf
        assert int(w) == nth[kind]
        nth[kind] += 1


def test_the_published_config_counts_its_parameters():
    """ISSUE 37's count, leaf by leaf: 23,843,661,440 with the head tied
    and each expert layer's 64 bias entries counted; ``num_params`` reads
    2,048 over it, a bias it counts on the final RMSNorm (as for every
    RMSNorm model: ``test_latent_moe_serving``)."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert cfg.layer_kinds == ("conv", "conv", "full", "conv") * 10
    assert (cfg.first_dense_layers, cfg.n_experts, cfg.moe_top_k,
            cfg.conv_taps, cfg.moe_route_norm_eps) == (2, 64, 4, 3, 1e-6)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.ffn_size,
            cfg.moe_ffn, cfg.moe_shared_size, cfg.vocab_size) == (
        32, 8, 64, 11776, 1536, 0, 65536)
    assert cfg.qk_norm and cfg.tie_embeddings and cfg.moe_gate_bias
    assert cfg.rope_theta == 1e6 and cfg.max_seq_len == 128000
    assert cfg.moe_score_func == "sigmoid" and cfg.moe_route_scale == 1.0
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape)) for p, x in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert sum(sizes.values()) == 23_843_661_440
    assert cfg.num_params() == 23_843_661_440 + cfg.hidden_size
    conv = sum(v for k, v in sizes.items() if "['blocks']['conv']" in k) // 28
    attn = sum(v for k, v in sizes.items() if "['blocks']['attn']" in k) // 10
    assert (conv, attn) == (16_783_360, 10_485_888)
    assert sizes["['blocks']['w_up']"] // (38 * 64) * 3 == 9_437_184
    assert sizes["['tok_emb']"] == 134_217_728
    # the benchmark's cut: the first ten layers, kinds as published
    cut = dict(row["config"], num_hidden_layers=10,
               layer_types=row["config"]["layer_types"][:10])
    c = config_from_hf(types.SimpleNamespace(**cut))
    assert c.num_params() - c.hidden_size == 5_267_090_176
    assert c.segments[1][1].layer_kinds == ("full", "conv", "conv",
                                            "conv") * 2
    bad = dict(row["config"], layer_types=row["config"]["layer_types"][:7])
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(types.SimpleNamespace(**bad))


def test_state_dict_under_the_family_s_names_imports(model):
    """A fabricated ``lfm2_moe`` state dict (the family's tensor names,
    torch layout ``[out, in]``, the convolution ``[channels, 1, taps]``)
    gives back the tree it was made from."""
    cfg, params, toks, whole, _ = model
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.embedding_norm.weight": params["final_norm"]["scale"]}
    attn = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "out_proj"}
    mlp = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    d = cfg.first_dense_layers
    nth = {}
    for i, kind in enumerate(cfg.layer_kinds):
        key, at = ("dense_blocks", i) if i < d else ("blocks", i - d)
        blocks = params[key]
        mixer = T.mixer_of(kind)
        j = nth.get((key, mixer), 0)
        nth[(key, mixer)] = j + 1
        mp = jax.tree.map(lambda a: a[j], blocks[mixer])
        pre = f"model.layers.{i}."
        sd[pre + "operator_norm.weight"] = blocks["ln1"]["scale"][at]
        sd[pre + "ffn_norm.weight"] = blocks["ln2"]["scale"][at]
        if kind == "conv":
            sd[pre + "conv.in_proj.weight"] = mp["w_in"].T
            sd[pre + "conv.conv.weight"] = mp["conv_w"].T[:, None, :]
            sd[pre + "conv.out_proj.weight"] = mp["wo"].T
        else:
            for ours, theirs in attn.items():
                sd[pre + f"self_attn.{theirs}.weight"] = mp[ours].T
            sd[pre + "self_attn.q_layernorm.weight"] = mp["q_norm"]
            sd[pre + "self_attn.k_layernorm.weight"] = mp["k_norm"]
        ff = pre + "feed_forward."
        if i < d:
            for ours, theirs in mlp.items():
                sd[ff + theirs + ".weight"] = blocks[ours][at].T
            continue
        sd[ff + "gate.weight"] = blocks["gate_w"][at].T
        sd[ff + "expert_bias"] = blocks["gate_bias"][at]
        for ours, theirs in mlp.items():
            for e in range(cfg.n_experts):
                sd[ff + f"experts.{e}.{theirs}.weight"] = blocks[ours][at, e].T
    sd = {k: np.asarray(v) for k, v in sd.items()}
    got = params_from_lfm2_moe(sd, cfg)
    H.assert_same_tree(params, got)
    with jax.default_matmul_precision("highest"):
        assert rel(T.forward(got, jnp.asarray(toks), cfg), whole) < TOL
    name = next(n for n, hf in MODELS.items()
                if config_from_hf(types.SimpleNamespace(**hf)) == cfg)
    assert import_hf_model((sd, types.SimpleNamespace(**MODELS[name])))[0] \
        == cfg


def test_the_tick_s_span_and_gauges_say_what_state_was_written(
        cut, monkeypatch):
    from deepspeed_tpu import telemetry

    cfg, params, toks, _ = cut
    eng = H.engine(FAMILY, cfg, params)
    eng.put([1, 2], [toks[0, :20].tolist(), toks[1, :5].tolist()])
    spans = H.spy_on_spans(monkeypatch, "decode_tick")
    eng.step()        # 16 rows: one chunk of the first prompt
    eng.step()        # the rest of it + the second prompt whole
    eng.step()        # two decode rows
    assert [s["conv_state_rows"] for s in spans] == [1, 2, 2]
    assert [s["state_slots"] for s in spans] == [1, 2, 2]
    per_slot = telemetry.gauge("fastgen_state_bytes_per_slot")
    n_conv = cfg.layer_kinds.count("conv")
    assert per_slot.value(kind="conv") == n_conv * 2 * cfg.hidden_size * 4
    eng.flush([1, 2])


def test_a_row_that_needs_no_block_leaves_the_free_list_alone(cut):
    """A tick of hundreds of decode rows asks ``_ensure_blocks`` once a
    row; all but one in ``block_size`` need nothing, and those do not
    touch the allocator (a copy of its free list a row was most of a
    256-row tick's scheduling time on the chip: PERF.md, PR 37)."""
    cfg, params, toks, _ = cut
    eng = H.engine(FAMILY, cfg, params)
    eng.put([1], [toks[0, :6].tolist()])
    eng.step()
    seq, free = eng.seqs[1], eng.allocator._free
    calls = []
    eng.allocator.grow = lambda n=1: calls.append(n) or []
    assert eng._ensure_blocks(seq, seq.pos) and not calls
    assert eng.allocator._free is free
    del eng.allocator.grow
    before = list(free)
    assert eng._ensure_blocks(seq, 8) and eng.allocator._free is free
    assert seq.blocks[-1] == before[0] and list(free) == before[1:]
    # the tokens a tick kept are counted once a tick, not once a row
    from deepspeed_tpu import telemetry

    total = telemetry.counter("fastgen_generated_tokens_total")
    n0 = total.total()
    out = eng.step()
    assert total.total() - n0 == len(out) == 1
    eng.flush([1])


def test_the_router_divides_as_published():
    """``w / (sum + 1e-6)`` where the repo's other routers divide by
    ``max(sum, 1e-9)``: told apart on scores small enough to show it, and
    the default left as it was."""
    logits = jnp.full((3, 8), -14.0).at[:, :4].add(
        jnp.arange(12.0).reshape(3, 4) * 0.1)
    kw = dict(k=4, score_func="sigmoid", normalize=True)
    old = topk_gating_indices(logits, **kw)
    new = topk_gating_indices(logits, normalize_eps=1e-6, **kw)
    scores = jax.nn.sigmoid(logits[:, :4])
    np.testing.assert_allclose(
        np.sort(new.weights, axis=1),
        np.sort(scores / (scores.sum(1, keepdims=True) + 1e-6), axis=1),
        rtol=1e-6)
    np.testing.assert_allclose(old.weights.sum(1), 1.0, rtol=1e-6)
    assert float(new.weights.sum(1).max()) < 0.9
    dense = topk_gating(logits, capacity_factor=8.0, normalize_eps=1e-6, **kw)
    np.testing.assert_allclose(dense.combine.sum((1, 2)), new.weights.sum(1),
                               rtol=1e-6)


test_entry_points_that_refuse_conv_layers = H.entry_points_refuse_test(
    FAMILY, STACKS)


def test_a_stack_with_conv_layers_refuses_what_it_does_not_write():
    cfg = config_from_hf(types.SimpleNamespace(**MODELS["cut"]))
    for wrong in (dict(use_bias=True), dict(attn_gate=True),
                  dict(post_norms=True), dict(conv_taps=1)):
        with pytest.raises(NotImplementedError, match="conv layers"):
            T.init_params(dataclasses.replace(cfg, **wrong),
                          jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="state_slots"):
        PG.init_paged_kv(cfg, 16, 4, state_slots=0)
