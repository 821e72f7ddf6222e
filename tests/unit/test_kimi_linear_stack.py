"""Delta-rule linear-attention layers (``kda``) and latent-attention layers
without rotary (``latent``) in ONE stack of standard blocks, over a leading
dense layer and expert layers that hold a share of their experts
(``TransformerConfig.standard_blocks``; the ``kimi_linear`` family,
Kimi-Linear-48B-A3B).

Toy widths (heads of 128 kept: the rule's tiles are the real ones),
float32, matmul precision "highest": the paged tick
(``models/paged.forward_paged`` over the engine's latent blocks and the
slots' state), the whole-sequence forward (``T.forward``) and the plain
reference (``benchmarks/reference/kimi_linear_lm.py``, which imports
nothing of the program and runs the recurrence one row after another) are
three implementations of the same equations and agree to rounding, ~1e-6
relative; the tolerance 2e-5 leaves room for the order of float32 sums
(the chunkwise form sums a chunk's rows in another order than the
recurrence) and none for a wrong decay, step size, tap, norm, rotation,
expert or state: every fault made on purpose below reads over a hundred
times the tolerance.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import kimi_linear_lm as R
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model)
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

CONFIG = "benchmarks/configs/kimi-linear-48b-a3b.json"


def _hf(kinds: str, **kw):
    """``kinds``: a letter a layer from layer 1, ``k`` or ``m``."""
    hf = dict(
        model_type="kimi_linear", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, num_hidden_layers=len(kinds),
        first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
        mla_use_nope=True, rope_scaling=None, rope_theta=10000,
        rms_norm_eps=1e-5,
        linear_attn_config={
            "full_attn_layers": [i + 1 for i, k in enumerate(kinds)
                                 if k == "m"],
            "kda_layers": [i + 1 for i, k in enumerate(kinds) if k == "k"],
            "head_dim": 128, "num_heads": 2, "short_conv_kernel_size": 4},
        num_experts=4, router_experts=16, first_expert=0,
        num_experts_per_token=4, num_shared_experts=1, moe_renormalize=True,
        moe_router_activation_func="sigmoid", num_expert_group=1,
        topk_group=1, routed_scaling_factor=2.446, moe_layer_freq=1,
        tie_word_embeddings=False, vocab_size=128, model_max_length=4096)
    hf.update(kw)
    return hf

#: the benchmark's cut (the dense KDA layer, then two whole periods), the
#: published pattern cut where a period is not whole, a share of the
#: experts that does not start at the first, and the cut choosing 8 experts
#: a token (the mistakes' model: top-7 for top-8 is one of them)
FAMILY = H.Family(R, {
    "cut": _hf("kkkmkkkm"),
    "remainder": _hf("kkkmkkkmkk"),
    "a-later-share": _hf("kkmk", first_expert=8),
    "cut-top-8": _hf("kkkmkkkm", num_experts_per_token=8),
})
STACKS = ["a-later-share", "cut", "remainder"]
MODELS = {name: FAMILY.models[name] for name in STACKS}


@pytest.fixture(scope="module", params=STACKS)
def model(request):
    m = FAMILY.model(request.param)
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


@pytest.fixture(scope="module")
def cut():
    m = FAMILY.model("cut")
    return m.cfg, m.params, m.toks, m.arch


def _stores_hold_the_first_pair(eng):
    assert float(jnp.abs(eng.pool["kda"][:, 1:]).max(axis=(2, 3, 4)).min()) > 0


test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, STACKS)
test_paged_ticks_match_whole_forward_and_reference = H.paged_ticks_test(
    FAMILY, STACKS, n_prompt=30, cases=[
        (None, 13, TOL, {}),      # chunk and sequence boundaries fall mid-tick
        # the kernels (interpret mode) under the tick: ``kda_step`` is
        # exact, the latent kernel multiplies in bfloat16 by design
        (paged_attention, 13, 2e-3, {}),
        (None, 16, TOL, {}),      # a full tick
    ])
test_a_slot_handed_on_starts_from_zero = H.slot_handed_on_test(
    FAMILY, STACKS, _stores_hold_the_first_pair)
test_a_fault_in_the_state_is_seen = H.state_fault_test(
    FAMILY, "cut", times=1000, faults={
        "state-dropped-at-a-tick-boundary": "kda",
        "conv-inputs-dropped-at-a-tick-boundary": "kda_conv",
        "state-carried-into-the-next-sequence": H.CARRIED})
# each mistake the cell's notes list, on the first sequence
test_a_mistake_made_on_purpose_is_seen = H.reference_mistake_test(
    FAMILY, "cut-top-8", sequences=1, seen=lambda mistake: 100 * TOL,
    mistakes={"top-7-for-top-8": {"top_k": 7}, **{
        m: {"faults": frozenset([m])} for m in (
            "decay-dropped", "b-is-one", "taps-reversed", "no-l2norm",
            "rotary-on-latent")}})
test_two_sequences_decode_in_one_tick_and_a_slot_is_handed_on = \
    H.two_sequences_test(FAMILY, "cut")


def test_the_shares_add_up():
    """Eight shares of an expert layer (2 of 16 experts each, the shared
    expert counted once) give the reference's uncut layer."""
    hf = _hf("kkmk", num_experts=16, router_experts=16)
    cfg, params, _ = H.build(hf)
    arch = R.arch_from_config(hf, hf)
    seg = cfg.segments[1][1]
    lp = jax.tree.map(lambda a: a[0], {
        k: v for k, v in params["blocks"].items() if k not in T.MIXERS})
    u = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        stack = {k: params["blocks"][k] for k in ("w_gate", "w_up", "w_down")}
        want, _ = R._moe(u, R._f32(lp), stack, 0, arch)
        shared = R._mlp(u, lp["sw_gate"], lp["sw_up"], lp["sw_down"])
        total = shared
        for i in range(8):
            share = dataclasses.replace(seg, n_experts=2,
                                        moe_router_experts=16,
                                        moe_first_expert=2 * i)
            lp_i = {**lp, **{k: lp[k][2 * i:2 * i + 2]
                             for k in ("w_gate", "w_up", "w_down")}}
            total = total + T._ffn(u, lp_i, share)[0] - shared
    assert rel(total, want) < TOL


# --------------------------------------------------------------------------- #
# configuration, parameters, pools
# --------------------------------------------------------------------------- #

def test_segments_mixers_and_pools(model):
    cfg, params, *_ = model
    kinds, h = cfg.layer_kinds, cfg.hidden_size
    assert cfg.standard_blocks and cfg.mla and cfg.pos_emb == "none"
    assert cfg.first_dense_layers == 1 and kinds[0] == "kda"
    assert [(k, c.num_layers, bool(c.n_experts), c.layer_kinds)
            for k, c in cfg.segments] == [
        ("dense_blocks", 1, False, kinds[:1]),
        ("blocks", len(kinds) - 1, True, kinds[1:])]
    n_kda, n_lat = kinds[1:].count("kda"), kinds[1:].count("latent")
    blocks = params["blocks"]
    assert "attn" not in params["dense_blocks"]
    assert params["dense_blocks"]["kda"]["wq"].shape == (1, h, 256)
    assert blocks["kda"]["conv_k"].shape == (n_kda, 4, 256)
    assert blocks["kda"]["a_log"].shape == (n_kda, 2)
    assert blocks["kda"]["dt_bias"].shape == (n_kda, 256)
    assert blocks["attn"]["wq"].shape == (n_lat, h, 4 * 24)
    assert blocks["attn"]["wkv_a"].shape == (n_lat, h, 40)
    assert "wq" not in blocks and blocks["ln1"]["scale"].shape[0] \
        == len(kinds) - 1
    assert blocks["gate_w"].shape == (len(kinds) - 1, h, 16)
    assert blocks["w_up"].shape[:2] == (len(kinds) - 1, 4)
    assert "lm_head" in params
    # latent blocks for the latent layers, a matrix a head and the three
    # convolutions' inputs a slot for the kda layers; nothing else
    pool = PG.init_paged_kv(cfg, 40, 4, state_slots=3, max_run=16)
    assert set(pool) == {"latent", "kda", "kda_conv"}
    assert pool["latent"].shape == (kinds.count("latent"), 40, 4, 128)
    assert pool["kda"].shape == (kinds.count("kda"), 4, 2, 128, 128)
    assert pool["kda"].dtype == jnp.float32
    # a row of the store is ONE stored input of one slot (inputs-major):
    # rows second-minor, lanes full, the bytes a slot unchanged
    assert pool["kda_conv"].shape == (kinds.count("kda") * 3 * 4, 3 * 256)
    assert pool["kda_conv"].nbytes // 4 == kinds.count("kda") * int(
        np.prod(HY.kda_state_shapes(cfg)[1])) * 4
    H.assert_axes_name_every_leaf(cfg, params)
    # (num_params counts a bias on the final RMSNorm: test_latent_moe_serving)
    assert cfg.num_params() - h == sum(
        x.size for x in jax.tree.leaves(params))


def test_the_published_config_counts_its_parameters():
    """49.12 B for the published model, and the benchmark's cut the count
    its file states; every width of the file is the catalog's."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    published = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert published.num_layers == 27
    assert published.layer_kinds.count("kda") == 20
    assert published.layer_kinds[3] == published.layer_kinds[26] == "latent"
    assert published.num_params() == 49_122_684_032
    assert round(published.num_params() / 1e9, 2) == 49.12
    with open(CONFIG) as f:
        config = json.load(f)
    from benchmarks import model_config

    cut = model_config.build(config, "serve")
    assert cut.num_params() == config["bytes"]["num_params_as_run"]
    assert cut.num_params() - cut.hidden_size \
        == config["bytes"]["parameters_as_run"]
    assert cut.layer_kinds == ("kda", "kda", "kda", "latent") * 2
    assert (cut.n_experts, cut.router_experts, cut.moe_top_k) == (32, 256, 8)
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == {"num_hidden_layers", "linear_attn_config",
                       "num_experts", "vocab_size"}
    la, theirs = config["linear_attn_config"], \
        row["config"]["linear_attn_config"]
    assert {k for k in theirs if la[k] != theirs[k]} \
        == {"kda_layers", "full_attn_layers"}
    rule, conv = HY.kda_state_shapes(cut)
    assert 4 * int(np.prod(rule)) == 2_097_152
    assert 2 * int(np.prod(conv)) == 73_728
    assert 6 * (2_097_152 + 73_728) == config["bytes"][
        "state_bytes_a_sequence"]


def _state_dict(cfg, params):
    """The program's tree under the family's tensor names."""
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T}
    index = 0
    for key, seg in cfg.segments:
        blocks, seen = params[key], {"kda": 0, "attn": 0}
        for layer, kind in enumerate(seg.layer_kinds):
            pre = f"model.layers.{index}."
            sd[pre + "input_layernorm.weight"] = blocks["ln1"]["scale"][layer]
            sd[pre + "post_attention_layernorm.weight"] = \
                blocks["ln2"]["scale"][layer]
            mixer = T.mixer_of(kind)
            mp = jax.tree.map(lambda a: a[seen[mixer]], blocks[mixer])
            seen[mixer] += 1
            at = pre + "self_attn."
            if kind == "kda":
                from deepspeed_tpu.models.hf_import import _KDA_TENSORS

                for ours, (theirs, matrix) in _KDA_TENSORS.items():
                    sd[at + theirs] = mp[ours].T if matrix else mp[ours]
                sd[at + "A_log"] = mp["a_log"].reshape(1, 1, -1, 1)
                for x in "qkv":
                    sd[at + f"{x}_conv1d.weight"] = mp[f"conv_{x}"].T[:, None]
            else:
                for ours, theirs in (("wq", "q_proj"), ("wo", "o_proj"),
                                     ("wkv_a", "kv_a_proj_with_mqa"),
                                     ("wkv_b", "kv_b_proj")):
                    sd[at + theirs + ".weight"] = mp[ours].T
                sd[at + "kv_a_layernorm.weight"] = mp["kv_a_norm"]
            names = (("w_gate", "gate_proj", "w1"), ("w_up", "up_proj", "w3"),
                     ("w_down", "down_proj", "w2"))
            if not seg.n_experts:
                for ours, theirs, _ in names:
                    sd[pre + f"mlp.{theirs}.weight"] = blocks[ours][layer].T
            else:
                moe = pre + "block_sparse_moe."
                sd[moe + "gate.weight"] = blocks["gate_w"][layer].T
                sd[moe + "gate.e_score_correction_bias"] = \
                    blocks["gate_bias"][layer]
                for ours, theirs, short in names:
                    sd[moe + f"shared_experts.{theirs}.weight"] = \
                        blocks["s" + ours][layer].T
                    for e in range(seg.n_experts):
                        sd[moe + f"experts.{seg.moe_first_expert + e}."
                           f"{short}.weight"] = blocks[ours][layer, e].T
            index += 1
    return {k: np.asarray(v) for k, v in sd.items()}


def test_state_dict_under_the_family_s_names_imports(model):
    cfg, params, *_ = model
    hf = next(h for h in MODELS.values()
              if config_from_hf(types.SimpleNamespace(**h)) == cfg)
    got_cfg, got = import_hf_model((_state_dict(cfg, params),
                                    types.SimpleNamespace(**hf)))
    assert got_cfg == cfg
    H.assert_same_tree(params, got)


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def test_the_tick_s_span_and_gauges_say_which_form_took_which_rows(
        cut, monkeypatch):
    from deepspeed_tpu import telemetry

    cfg, params, toks, _ = cut
    eng = H.engine(FAMILY, cfg, params)
    counter = telemetry.counter("fastgen_kda_rows_total")
    before = {f: counter.value(form=f) for f in ("step", "chunk")}
    pieces = telemetry.counter("fastgen_kda_chunk_pieces_total")
    pieces_before = pieces.value()
    eng.put([1, 2, 3], [toks[0, :20].tolist(), toks[1, :5].tolist(),
                        toks[0, 7:8].tolist()])
    spans = H.spy_on_spans(monkeypatch, "decode_tick")
    eng.step()    # 16 rows: one chunk of the first prompt
    eng.step()    # its last 4 rows, the second prompt whole, the third
    eng.step()    # three decode rows
    assert [s["kda_step_rows"] for s in spans] == [0, 1, 3]
    assert [s["kda_chunk_rows"] for s in spans] == [16, 9, 0]
    # a chunk of 64 rows each run of the chunk form has a row in: the
    # first prompt's 16 rows; its last 4 and the second prompt's 5
    assert [s["kda_chunk_pieces"] for s in spans] == [1, 2, 0]
    assert [s["kda_state_rows"] for s in spans] == [1, 3, 3]
    assert counter.value(form="step") - before["step"] == 4
    assert counter.value(form="chunk") - before["chunk"] == 25
    assert pieces.value() - pieces_before == 3
    n_kda = cfg.layer_kinds.count("kda")
    per_slot = telemetry.gauge("fastgen_state_bytes_per_slot")
    assert per_slot.value(kind="rule") == n_kda * 2 * 128 * 128 * 4
    assert per_slot.value(kind="conv") == n_kda * 3 * 768 * 4
    assert telemetry.gauge("fastgen_state_bytes").value() == 4 * (
        per_slot.value(kind="rule") + per_slot.value(kind="conv"))
    eng.flush([1, 2, 3])


def test_the_default_slots_reckon_a_slot_s_bytes(cut):
    """A slot of this stack is megabytes (LFM2's is kilobytes): the default
    count is what takes no more memory than the blocks (or 256 MiB), not
    ``token_budget // 8`` whatever it costs."""
    cfg, params, *_ = cut
    blocks, state = FastGenEngine._pool_bytes(cfg, 64, 4, 3, 16)
    assert blocks == 2 * 64 * 4 * 128 * 4
    assert state == 6 * 4 * (2 * 128 * 128 * 4 + 3 * 768 * 4)
    wide = dataclasses.replace(cfg, kda_heads=64)
    eng = FastGenEngine(wide, H.init_params(wide, jax.random.PRNGKey(0)),
                        n_blocks=4096, block_size=4, max_blocks_per_seq=16,
                        token_budget=1024, use_pallas_kernel=False)
    per_slot = 6 * (64 * 128 * 128 * 4 + 3 * 3 * 64 * 128 * 4)
    assert eng.allocator.state_slots == (256 << 20) // per_slot < 1024 // 8


def test_a_pool_that_does_not_fit_says_what_takes_what(cut, monkeypatch):
    cfg, params, *_ = cut
    device = jax.devices()[0]
    monkeypatch.setattr(type(device), "memory_stats",
                        lambda self: {"bytes_limit": 1 << 20}, raising=False)
    with pytest.raises(ValueError, match=r"blocks of 4 take .* GB and 3 "
                                         r"sequence slots' state"):
        H.engine(FAMILY, cfg, params)


test_entry_points_that_refuse_the_stack = H.entry_points_refuse_test(
    FAMILY, ["cut"], match="layer kinds|layer_kinds|MLA")


def test_a_stack_of_kinds_refuses_precisely_what_it_does_not_write(cut):
    cfg, *_ = cut
    key = jax.random.PRNGKey(0)
    with pytest.raises(NotImplementedError, match="latent and grouped-query"):
        T.init_params(dataclasses.replace(
            cfg, first_dense_layers=0,
            layer_kinds=("kda", "full") * 4), key)
    with pytest.raises(NotImplementedError, match="is the kind `latent`"):
        T.init_params(dataclasses.replace(cfg, mla=False), key)
    with pytest.raises(NotImplementedError, match="kda layers stand"):
        T.init_params(dataclasses.replace(cfg, use_bias=True), key)
    with pytest.raises(ValueError, match="kda_heads"):
        T.init_params(dataclasses.replace(cfg, kda_rank=0), key)
    with pytest.raises(ValueError, match="state_slots"):
        PG.init_paged_kv(cfg, 16, 4, state_slots=0)
    with pytest.raises(ValueError, match="unsupported HF architecture"):
        config_from_hf(types.SimpleNamespace(model_type="kimi_vl"))
    with pytest.raises(NotImplementedError, match="mla_use_nope"):
        config_from_hf(types.SimpleNamespace(
            **{**MODELS["cut"], "mla_use_nope": False}))
