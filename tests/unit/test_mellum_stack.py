"""A stack of window and full attention layers over expert layers that hold
a SHARE of their experts, TRAINED: the ``mellum`` family (JetBrains Mellum
2: the Qwen3-MoE block, rotary by layer type, YaRN on the full layers).

Toy widths, float32, matmul precision "highest": the system's forward,
loss and gradients (``dst.causal_lm_spec`` with the plain attention and
with the flash kernel under its window) and the plain reference
(``benchmarks/reference/mellum_lm.py``, which imports nothing of the
program) are two implementations of the same equations and agree to
rounding, ~1e-6 relative; the tolerance 2e-5 leaves room for the order of
float32 sums and none for a wrong mask, rotary table, expert or weight.
"""
import dataclasses
import functools
import json
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from benchmarks.reference import mellum_lm as R
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model,
                                            params_from_mellum)
from deepspeed_tpu.moe import layer as MOE

import family_harness as H
from family_harness import CATALOG, TOL

CONFIG = "benchmarks/configs/mellum2-12b-a2.5b.json"
_ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                            "factor": 16,
                            "original_max_position_embeddings": 8192,
                            "beta_fast": 32, "beta_slow": 1,
                            "attention_factor": 1.2772588722239782},
         "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def _hf(**kw):
    """One period (sliding x 3, full) at toy widths; 4 of 16 experts held
    from expert 4 unless told otherwise."""
    hf = dict(model_type="mellum", hidden_size=64, intermediate_size=160,
              moe_intermediate_size=32, head_dim=16, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=4,
              layer_types=["sliding_attention"] * 3 + ["full_attention"],
              mlp_layer_types=["sparse"] * 4, num_experts=4,
              router_experts=16, first_expert=4, num_experts_per_tok=4,
              norm_topk_prob=True, rms_norm_eps=1e-6, rope_parameters=_ROPE,
              sliding_window=16, use_sliding_window=True,
              attention_bias=False, tie_word_embeddings=False,
              vocab_size=128, max_position_embeddings=131072)
    hf.update(kw)
    return hf


# differs from the harness's ``build``: typed keys and a seed a leaf, a
# config in float32 under full remat, an ``arch`` of the file's ``assumed``
def _model(hf, seed=0):
    cfg = dataclasses.replace(config_from_hf(types.SimpleNamespace(**hf)),
                              dtype="float32", remat="full")
    params = H.init_params(cfg, jax.random.key(seed))
    # norms off one and every leaf off its initial law: a dropped gain or
    # branch must show
    leaves, tree = jax.tree.flatten(params)
    with H.drawn_whole():
        params = tree.unflatten([
            x + 0.05 * jax.random.normal(jax.random.key(100 + i), x.shape)
            for i, x in enumerate(leaves)])
    arch = R.arch_from_config({"assumed": {}}, hf)
    return cfg, params, arch


# differs from the harness's ``rel``: gradients are compared leaf by leaf by
# their largest entry, not by their norm
def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _tokens(cfg, S=48, B=2, seed=7):
    return jax.random.randint(jax.random.key(seed), (B, S), 0, cfg.vocab_size)


# ------------------------------------------------------------------ #
# (a) the system against the reference: logits, loss, every gradient
# ------------------------------------------------------------------ #
CASES = {"share-from-4": {}, "share-from-0": dict(first_expert=0),
         "every-expert": dict(num_experts=16, router_experts=16,
                              first_expert=0)}


@functools.lru_cache(maxsize=None)
def _case(case):
    """A case's model and tokens with the reference's logits, loss and
    gradients: once a case, whatever attention the system runs."""
    cfg, params, arch = _model(_hf(**CASES[case]))
    tokens = _tokens(cfg)
    # (ONE program: differentiated eagerly the reference is a compile an
    # operation, fifteen hundred of them a case)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: R.loss(p, tokens, arch)))(params)
    return (cfg, params, arch, tokens,
            R.forward_logits(params, tokens, arch), ref_loss, ref_grads)


@pytest.mark.parametrize("attention", [None, "flash"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_system_matches_reference(case, attention):
    cfg, params, arch, tokens, ref_logits, ref_loss, ref_grads = _case(case)
    spec = dst.causal_lm_spec(cfg, attention=attention, loss_impl="exact")
    with jax.default_matmul_precision("highest"):
        logits = spec.apply_fn(params, {"tokens": tokens})
        loss, grads = jax.jit(jax.value_and_grad(spec.loss_fn))(
            params, {"tokens": tokens})
    assert _rel(logits, ref_logits) < TOL
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 15
    for (path, g), r in zip(flat, ref_flat):
        assert _rel(g, r) < TOL, jax.tree_util.keystr(path)
    # the router learns: through the weights of the pairs here and through
    # the balance term
    assert float(jnp.max(jnp.abs(grads["blocks"]["gate_w"]))) > 0


MISTAKES = {"no-window": 1e-3, "no-yarn": 1e-3, "no-renorm": 1e-3,
            "share-off-by-one": 1e-3}


@functools.lru_cache(maxsize=None)
def _whole_forward(case):
    cfg, params, _, tokens, *_ = _case(case)
    with jax.default_matmul_precision("highest"):
        return T.forward(params, tokens, cfg)


@pytest.mark.parametrize("mistake", sorted(MISTAKES))
def test_a_mistaken_reference_is_seen(mistake):
    _, params, arch, tokens, *_ = _case("share-from-4")
    wrong = dict(arch, faults=(mistake,))
    assert _rel(_whole_forward("share-from-4"),
                R.forward_logits(params, tokens, wrong)) > MISTAKES[mistake]


# ------------------------------------------------------------------ #
# (b) the shares add up, forward and backward
# ------------------------------------------------------------------ #
def _one_layer(first, held):
    hf = _hf(num_hidden_layers=1, layer_types=["sliding_attention"],
             mlp_layer_types=["sparse"], num_experts=16, router_experts=16,
             first_expert=0)
    cfg, params, arch = _model(hf)
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    share = dict(lp, **{k: lp[k][first:first + held]
                        for k in ("w_up", "w_gate", "w_down")})
    return cfg, lp, share, arch


def _routed(cfg, lp, u, first):
    y, aux = MOE.moe_ffn(
        u, lp["gate_w"], {k: lp[k] for k in ("w_up", "w_gate", "w_down")},
        activation="swiglu", k=cfg.moe_top_k, dispatch="ragged",
        route_norm=cfg.moe_route_norm, first_expert=first)
    return y, aux


def test_four_shares_sum_to_the_uncut_layer():
    cfg, lp, _, arch = _one_layer(0, 16)
    u = jax.random.normal(jax.random.key(3), (2, 40, cfg.hidden_size))
    ct = jax.random.normal(jax.random.key(4), u.shape)
    with jax.default_matmul_precision("highest"):
        ref, _ = R._experts(u.reshape(-1, cfg.hidden_size), jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), lp), arch)
        whole, vjp_whole = jax.vjp(
            lambda u, lp: _routed(cfg, lp, u, 0)[0], u, lp)
        parts, grads = [], []
        for first in range(0, 16, 4):
            share = dict(lp, **{k: lp[k][first:first + 4]
                                for k in ("w_up", "w_gate", "w_down")})
            y, vjp = jax.vjp(lambda u, s: _routed(cfg, s, u, first)[0],
                             u, share)
            parts.append(y)
            grads.append(vjp(ct))
        du, dlp = vjp_whole(ct)
    assert _rel(sum(parts), ref.reshape(u.shape)) < TOL
    assert _rel(sum(parts), whole) < TOL
    # the rows' gradient and the router's are sums over the shares; a
    # share's experts' gradients are the whole layer's own slice
    assert _rel(sum(g[0] for g in grads), du) < TOL
    assert _rel(sum(g[1]["gate_w"] for g in grads), dlp["gate_w"]) < TOL
    for k in ("w_up", "w_gate", "w_down"):
        assert _rel(jnp.concatenate([g[1][k] for g in grads]), dlp[k]) < TOL


def test_balance_term_is_over_the_routers_whole_width():
    cfg, lp, share, _ = _one_layer(4, 4)
    u = jax.random.normal(jax.random.key(5), (1, 64, cfg.hidden_size))
    assert float(_routed(cfg, share, u, 4)[1]) == pytest.approx(
        float(_routed(cfg, lp, u, 0)[1]), rel=1e-6)


@pytest.mark.parametrize("router,held,first,top_k", [
    (16, 4, 4, 4), (16, 4, 12, 8), (64, 16, 0, 8), (24, 8, 8, 6)])
def test_a_share_drawn_from_scratch_is_an_equal_share(router, held, first,
                                                      top_k):
    """``init_params`` of a share: the router's columns are the held
    experts' repeated over its width, so whatever a row is, its ``top_k``
    hold ``top_k * held / router`` experts of ANY share (a cell's work is
    then its shapes', not its seed's draw); an uncut router is a draw a
    column."""
    hf = _hf(num_experts=held, router_experts=router, first_expert=first,
             num_experts_per_tok=top_k)
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    gate = T.init_params(cfg, jax.random.key(3))["blocks"]["gate_w"]
    assert gate.shape == (4, 64, router)
    rows = jax.random.normal(jax.random.key(4), (512, 64))
    for layer in range(4):
        _, chosen = jax.lax.top_k(
            jax.nn.softmax(rows @ gate[layer], axis=-1), top_k)
        for share in range(0, router, held):
            here = ((chosen >= share) & (chosen < share + held)).sum(-1)
            assert np.all(np.asarray(here) == top_k * held // router)
    whole = config_from_hf(types.SimpleNamespace(**_hf(
        num_experts=router, router_experts=router, first_expert=0)))
    g = np.asarray(T.init_params(whole, jax.random.key(3))["blocks"]["gate_w"])
    assert not np.array_equal(g[..., :held], g[..., held:2 * held])


@pytest.mark.parametrize("skew", [0.0, 6.0])
def test_grouped_matmuls_get_the_groups_as_they_fall(skew):
    """A row a pair, and the grouped matmuls are handed the held experts'
    groups as the routing made them: the pairs that are not here lie behind
    the groups, in none (no tile of the kernel is spent on them). Under an
    even router a share of the pairs is here; under one that sends every
    row to the held experts all are, and the share IS the layer. No pair
    is dropped either way."""
    cfg, lp, share, _ = _one_layer(0, 4)
    u = jnp.abs(jax.random.normal(jax.random.key(6),
                                  (2, 64, cfg.hidden_size)))
    share = dict(share, gate_w=share["gate_w"].at[:, :4].add(skew))
    lp = dict(lp, gate_w=share["gate_w"])
    seen, real = [], MOE.grouped_dot

    def counting(x, w, group_sizes, *a, **kw):
        seen.append((x.shape[0], group_sizes))
        return real(x, w, group_sizes, *a, **kw)

    MOE.grouped_dot = counting
    try:
        part = _routed(cfg, share, u, 0)[0]
    finally:
        MOE.grouped_dot = real
    pairs = u.shape[0] * u.shape[1] * cfg.moe_top_k
    gate = MOE._gate_indices(u.reshape(-1, cfg.hidden_size), share["gate_w"],
                             None, cfg.moe_top_k, "softmax", True, 1, 1)
    here = [int(jnp.sum(gate.experts == e)) for e in range(4)]
    assert len(seen) == 3
    for rows, sizes in seen:
        assert rows == pairs
        assert [int(n) for n in sizes] == here
    if skew:        # every pair is here: the share IS the layer
        assert sum(here) == pairs
        assert _rel(part, _routed(cfg, lp, u, 0)[0]) < TOL
    else:
        assert 0 < sum(here) < pairs
    grads = jax.grad(lambda u, s: jnp.sum(jnp.sin(_routed(cfg, s, u, 0)[0])),
                     (0, 1))(u, share)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_rows_of_pairs_that_are_not_here_reach_no_gradient():
    """The rows behind the held pairs hold another expert's results,
    forward and backward: whatever they hold, nothing moves (the sum masks
    them out and their cotangents are exactly zero)."""
    cfg, lp, share, _ = _one_layer(4, 4)
    u = jax.random.normal(jax.random.key(8), (1, 64, cfg.hidden_size))
    experts = {k: share[k] for k in ("w_up", "w_gate", "w_down")}
    real = MOE.held_group_sizes
    held_rows = []

    def watched(idx, held, first):
        out = real(idx, held, first)
        held_rows.append(jnp.sum(out[2]))
        return out

    real_dot = MOE.grouped_dot

    def poisoned(x, w, group_sizes, *a, **kw):
        out = real_dot(x, w, group_sizes, *a, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < held_rows[-1], out, 777.0)

    def run(u, experts):
        y, _ = MOE.moe_ffn(u, share["gate_w"], experts, activation="swiglu",
                           k=cfg.moe_top_k, first_expert=4)
        return jnp.sum(y * y)

    want = jax.value_and_grad(run, (0, 1))(u, experts)
    MOE.held_group_sizes, MOE.grouped_dot = watched, poisoned
    try:
        got = jax.value_and_grad(run, (0, 1))(u, experts)
    finally:
        MOE.held_group_sizes, MOE.grouped_dot = real, real_dot
    assert int(held_rows[-1]) < u.shape[1] * cfg.moe_top_k
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert _rel(a, b) < TOL


# ------------------------------------------------------------------ #
# (d) rotary by kind against the reference's tables
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind,section", [("window", "sliding_attention"),
                                          ("full", "full_attention")])
def test_rotary_tables_by_kind(kind, section):
    cfg, _, _ = _model(_hf(head_dim=128, num_attention_heads=2,
                           num_key_value_heads=1))
    theta, scaling = cfg.rope_of(kind)
    inv, att = R._rope_inv_freq(128, _ROPE[section])
    ours, our_att = T._scaled_inv_freq(128, theta, scaling)
    np.testing.assert_allclose(ours, inv, rtol=2e-6)
    assert our_att == att
    cos, sin = T.rope_table(256, 128, theta, scaling)
    ang = jnp.arange(256, dtype=jnp.float32)[:, None] * inv
    np.testing.assert_allclose(cos, jnp.cos(ang) * att, atol=5e-5)
    np.testing.assert_allclose(sin, jnp.sin(ang) * att, atol=5e-5)
    assert att == (1.2772588722239782 if kind == "full" else 1.0)
    if kind == "full":      # YaRN moved the low frequencies, not the high
        plain, _ = R._rope_inv_freq(128, _ROPE["sliding_attention"])
        assert float(inv[0]) == float(plain[0])
        assert float(inv[-1]) == pytest.approx(float(plain[-1]) / 16)


def test_a_kind_without_rotary_is_the_case_none():
    cfg = dataclasses.replace(_model(_hf())[0], kind_rope=(("full", None),))
    assert cfg.rope_of("full") is None
    assert cfg.rope_of("window") == (500000.0, None)


# ------------------------------------------------------------------ #
# (e) the importer's row
# ------------------------------------------------------------------ #
def _catalog_config():
    with open(CATALOG) as f:
        return next(json.loads(line) for line in f
                    if "Mellum2-12B" in line)["config"]


def _hand_count(layers, held, vocab):
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = attention + 2 * 128 + 2 * 2304 + 2304 * 64 \
        + held * 3 * 2304 * 896
    # a norm's place counts twice its gain (``num_params``' own rule)
    return layers * layer + 2 * vocab * 2304 + 2 * 2304


@pytest.mark.parametrize("form", ["namespace", "transformers"])
def test_config_from_the_catalog(form):
    hf = _catalog_config()
    if form == "transformers":
        transformers = pytest.importorskip("transformers")
        source = transformers.PretrainedConfig(**hf)
    else:
        source = types.SimpleNamespace(**hf)
    cfg = config_from_hf(source)
    assert cfg.layer_kinds == ("window", "window", "window", "full") * 7
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim) \
        == (2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.router_experts, cfg.moe_top_k, cfg.moe_ffn) \
        == (64, 64, 8, 896)
    assert cfg.attn_window == 1024 and cfg.moe_route_norm and cfg.qk_norm
    assert cfg.rope_of("window") == (500000.0, None)
    theta, scaling = cfg.rope_of("full")
    assert theta == 500000.0 and scaling["factor"] == 16 \
        and scaling["attention_factor"] == 1.2772588722239782
    assert cfg.num_params() == _hand_count(28, 64, 98304) == 12_149_925_376


def test_the_cut_is_595m_parameters():
    from benchmarks import model_config

    with open(CONFIG) as f:
        config = json.load(f)
    cfg = model_config.build(config, "train", remat="full")
    assert cfg.num_params() == _hand_count(4, 16, 24576) == 595_156_480
    assert (cfg.n_experts, cfg.router_experts, cfg.moe_first_expert) \
        == (16, 64, 0)
    published = _catalog_config()
    changed = {k for k, v in published.items() if config.get(k) != v}
    assert changed == set(config["published"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_experts", "vocab_size"}


def test_params_round_trip_takes_the_shares_own_experts():
    hf = _hf()
    cfg, params, _ = _model(hf)
    blocks = params["blocks"]
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T}
    for i in range(cfg.num_layers):
        lyr = f"model.layers.{i}."
        sd[lyr + "input_layernorm.weight"] = blocks["ln1"]["scale"][i]
        sd[lyr + "post_attention_layernorm.weight"] = \
            blocks["ln2"]["scale"][i]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            sd[lyr + f"self_attn.{theirs}.weight"] = blocks[ours][i].T
        sd[lyr + "self_attn.q_norm.weight"] = blocks["q_norm"][i]
        sd[lyr + "self_attn.k_norm.weight"] = blocks["k_norm"][i]
        sd[lyr + "mlp.gate.weight"] = blocks["gate_w"][i].T
        for e in range(16):         # the checkpoint has every expert
            held = 4 <= e < 8
            for ours, theirs in (("w_gate", "gate_proj"),
                                 ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                w = blocks[ours][i, e - 4] if held \
                    else jnp.full(blocks[ours].shape[2:], float(e))
                sd[lyr + f"mlp.experts.{e}.{theirs}.weight"] = w.T
    got = params_from_mellum(sd, cfg)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b, jax.tree_util.keystr(path))
    cfg2, got2 = import_hf_model((sd, types.SimpleNamespace(**hf)))
    assert cfg2.layer_kinds == cfg.layer_kinds
    np.testing.assert_array_equal(got2["blocks"]["w_up"], blocks["w_up"])


def test_what_the_row_refuses():
    with pytest.raises(NotImplementedError, match="sparse"):
        config_from_hf(types.SimpleNamespace(
            **_hf(mlp_layer_types=["sparse"] * 3 + ["dense"])))
    with pytest.raises(ValueError, match="rope_parameters"):
        config_from_hf(types.SimpleNamespace(**_hf(rope_parameters={
            "sliding_attention": _ROPE["sliding_attention"]})))
    # a table a kind is trained and run whole; a paged tick has one table
    from deepspeed_tpu.models import paged as PG

    with pytest.raises(NotImplementedError, match="kind_rope"):
        PG.init_paged_kv(_model(_hf())[0], 8, 8, state_slots=2)


# ------------------------------------------------------------------ #
# (f) the step at 8,192 holds no [S, S] array
# ------------------------------------------------------------------ #
def test_the_step_at_8192_builds_no_score_matrix():
    """Every kind of the stack has a kernel, so nothing of the lowered
    loss-and-gradients program is ``[.., 8192, 8192]``: a window layer's
    mask lives in the kernel's tiles (plain jnp would hold 8.6 GB of float32
    scores a layer at 32 heads)."""
    S = 8192
    cfg = dataclasses.replace(_model(_hf(sliding_window=1024))[0],
                              dtype="bfloat16")
    spec = dst.causal_lm_spec(cfg, attention="flash")
    shapes = jax.eval_shape(spec.init_fn,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    text = jax.jit(jax.value_and_grad(spec.loss_fn)).lower(
        shapes, {"tokens": jax.ShapeDtypeStruct((1, S), jnp.int32)}
    ).as_text()
    assert not re.findall(rf"{S}x{S}[x>]", text)
    assert re.findall(rf"\dx{S}x\d+x16x", text)       # q is there
    # with the plain attention the same program does hold them
    plain = jax.jit(jax.value_and_grad(dst.causal_lm_spec(cfg).loss_fn)
                    ).lower(shapes, {"tokens": jax.ShapeDtypeStruct(
                        (1, S), jnp.int32)}).as_text()
    assert re.findall(rf"{S}x{S}[x>]", plain)
