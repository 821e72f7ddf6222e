"""A stack whose every layer is ONE norm and ONE sublayer
(``TransformerConfig.one_sublayer``; the ``nemotron_h`` family,
Nemotron-3-Super-120B-A12B): a Mamba-2 mixer (``mamba2``), grouped-query
attention without rotary (``full``) or an expert layer whose routed experts
take a latent of the row and activate by a squared ReLU (``ffn``), the
experts a share of the router's.

Toy widths (8 groups kept apart from the 16 heads, 9 experts a token of a
router 16 wide, a latent of 32 under a hidden of 64, two key-value heads
of 128: the pool's blocks lie heads first as the cell's), float32, matmul
precision "highest": the paged tick (``models/paged.forward_paged`` over
the engine's blocks and the slots' state), the whole-sequence forward
(``T.forward``) and the plain reference (``benchmarks/reference/
nemotron_h_lm.py``, which imports nothing of the program and runs the
recurrence one row after another) are three implementations of the same
equations and agree to rounding, ~1e-6 relative; the tolerance 2e-5 leaves
room for the order of float32 sums (the chunked form sums a chunk's rows in
another order than the recurrence) and none for a wrong decay, group, skip,
gate, norm, tap, activation, expert, scaling or state: every fault made on
purpose below reads over a hundred times the tolerance.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import nemotron_h_lm as R
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (_MAMBA2_TENSORS, config_from_hf,
                                            import_hf_model)
from deepspeed_tpu.moe import layer as ML
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention
from family_harness import CATALOG, TOL, rel

CONFIG = "benchmarks/configs/nemotron-3-super-120b-a12b.json"


def _hf(pattern: str, **kw):
    hf = dict(
        model_type="nemotron_h", hidden_size=64, head_dim=128,
        num_attention_heads=4, num_key_value_heads=2,
        num_hidden_layers=len(pattern), hybrid_override_pattern=pattern,
        mamba_num_heads=16, mamba_head_dim=8, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=16, expand=2,
        mamba_hidden_act="silu", use_conv_bias=True,
        intermediate_size=48, moe_intermediate_size=48, moe_latent_size=32,
        moe_shared_expert_intermediate_size=96, n_shared_experts=1,
        n_routed_experts=4, router_experts=16, first_expert=0,
        num_experts_per_tok=9, norm_topk_prob=True,
        routed_scaling_factor=5.0, n_group=1, topk_group=1,
        mlp_hidden_act="relu2", layer_norm_epsilon=1e-5, norm_eps=1e-5,
        attention_bias=False, mamba_proj_bias=False, mlp_bias=False,
        use_bias=False, rope_theta=10000, partial_rotary_factor=1,
        tie_word_embeddings=False, vocab_size=128,
        max_position_embeddings=4096)
    hf.update(kw)
    return hf


#: the benchmark's cut (one whole period), a stack that ends inside a
#: period, a share of the experts that does not start at the first, and a
#: layer of each kind (the engine's tick programs unroll a period: the
#: eleven layers of the benchmark's cut are the three-way comparisons')
FAMILY = H.Family(R, {
    "cut": _hf("MEMEMEMEM*E"),
    "remainder": _hf("M*EME"),
    "a-later-share": _hf("ME*E", first_expert=8),
    "a-layer-a-kind": _hf("ME*E"),
})
STACKS = ["a-later-share", "cut", "remainder"]
MODELS = {name: FAMILY.models[name] for name in STACKS}


@pytest.fixture(scope="module", params=STACKS)
def model(request):
    m = FAMILY.model(request.param)
    return m.cfg, m.params, m.toks, H.whole_forward(FAMILY, m), m.arch


@pytest.fixture(scope="module")
def cut():
    m = FAMILY.model("a-layer-a-kind")
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
    FAMILY, ["a-layer-a-kind"], _stores_hold_the_first_pair)
test_a_fault_in_the_state_is_seen = H.state_fault_test(
    FAMILY, "a-layer-a-kind", times=100, faults={
        "state-dropped-at-a-tick-boundary": "ssd",
        "conv-inputs-dropped-at-a-tick-boundary": "ssd_conv",
        "state-carried-into-the-next-sequence": H.CARRIED})
# the reference with one equation wrong against the system, here in float32
# (the least of nine chosen experts, of which a quarter are held, moves two
# layers' logits by 1.3e-3: sixty times the tolerance)
test_a_mistake_made_on_purpose_is_seen = H.reference_mistake_test(
    FAMILY, "a-layer-a-kind",
    seen=lambda mistake: (50 if mistake == "top-k-less-one" else 100) * TOL,
    mistakes={m: {"faults": frozenset({m})} for m in R.FAULTS})
test_two_sequences_decode_in_one_tick_and_a_slot_is_handed_on = \
    H.two_sequences_test(FAMILY, "a-layer-a-kind", both_decode=False)


# --------------------------------------------------------------------------- #
# the equations: shares, the published count
# --------------------------------------------------------------------------- #

def test_the_four_shares_add_up():
    """Four shares of an expert layer (4 of 16 experts each, the shared
    expert counted once; each share's routed part goes up through the
    latent's up-projection, which is linear) give the reference's uncut
    layer."""
    hf = _hf("ME*E", n_routed_experts=16, router_experts=16)
    cfg, params, _ = H.build(hf)
    arch = R.arch_from_config(hf, hf)
    lp = jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])
    u = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        stack = {k: params["blocks"]["ffn"][k] for k in ("w_up", "w_down")}
        want, _ = R._experts(u, R._f32(lp), stack, 0, arch)
        shared = R._linear(R._act(R._linear(u, lp["sw_up"]), arch),
                           lp["sw_down"])
        total = shared
        for i in range(4):
            share = dataclasses.replace(cfg, n_experts=4,
                                        moe_router_experts=16,
                                        moe_first_expert=4 * i)
            lp_i = {**lp, **{k: lp[k][4 * i:4 * i + 4]
                             for k in ("w_up", "w_down")}}
            total = total + T._ffn(u, lp_i, share)[0] - shared
    assert rel(total, want) < TOL


def test_the_published_config_counts_its_parameters():
    """The catalog's row through the importer: 120.67 B in all and 12.77 B
    a token (the model's own name, 120B-A12B), layer by layer as ISSUE 53
    counts them; the configuration file is that row but for what
    ``reduced`` names."""
    import json

    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cfg = config_from_hf(types.SimpleNamespace(**row["config"]))
    M, A, E, X = 109_640_064, 35_655_680, 54_530_560, 5_505_024
    embed = 2 * 131_072 * 4_096 + 4_096
    assert (cfg.layer_kinds.count("mamba2"), cfg.layer_kinds.count("ffn"),
            cfg.layer_kinds.count("full")) == (40, 40, 8)
    assert cfg.num_params() == 40 * M + 8 * A + 40 * (E + 512 * X) + embed \
        == 120_668_707_840
    assert cfg._sublayer_params(active=True) \
        == 40 * M + 8 * A + 40 * (E + 22 * X) + embed == 12_770_237_440
    file = json.load(open(CONFIG))
    assert file["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if file[k] != v}
    assert differs == set(file["reduced"]) == set(file["published"])
    assert all(file["published"][k] == row["config"][k] for k in differs)
    assert file["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"][27:38] == "MEMEMEMEM*E"
    run = config_from_hf(types.SimpleNamespace(
        **{k: v for k, v in file.items() if not isinstance(v, (dict, list))
           or k == "hybrid_override_pattern"}))
    assert run.num_params() == file["bytes"]["num_params_as_run"] \
        == 5 * M + A + 5 * (E + 128 * X) + 2 * 32_768 * 4_096 + 4_096
    # a slot's state and a position's keys and values, as the file says
    state, conv = HY.mamba2_state_shapes(run)
    assert 5 * (4 * int(np.prod(state)) + 2 * int(np.prod(conv))) \
        == file["bytes"]["state_bytes_a_sequence"] == 21_278_720


def test_a_layer_holds_no_leaves_for_the_half_it_lacks(model):
    cfg, params, *_ = model
    kinds = cfg.layer_kinds
    blocks = params["blocks"]
    assert cfg.one_sublayer and cfg.standard_blocks and not cfg.has_ln2
    assert set(blocks) == {"ln1", "mamba2", "attn", "ffn"}
    assert blocks["ln1"]["scale"].shape[0] == len(kinds)
    for sub, kind in (("mamba2", "mamba2"), ("attn", "full"), ("ffn", "ffn")):
        assert {a.shape[0] for a in jax.tree.leaves(blocks[sub])} \
            == {kinds.count(kind)}
    assert blocks["ffn"]["w_up"].shape[1:] == (4, 32, 48)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    # the one table: a state store and a convolution store for the mixers,
    # the two-head pool heads first, nothing for the feed-forward layers
    table = PG.cache_kinds(cfg)
    assert list(table) == ["full", "mamba2", "ffn"]
    assert [s.cls for s in table["mamba2"].stores] == [PG.SLOT, PG.CONV]
    assert table["full"].attend.heads_first and not table["ffn"].stores
    pool = PG.init_paged_kv(cfg, 8, 4, state_slots=3, max_run=16)
    assert pool["k"].shape == (1, 8, 2, 4, 128)
    assert pool["ssd"].shape == (kinds.count("mamba2"), 4, 8, 128, 16)
    assert pool["ssd_conv"].shape == (kinds.count("mamba2") * 3 * 4,
                                      16 * 8 + 2 * 8 * 128)


def _state_dict(cfg, params):
    """``params`` under the family's tensor names."""
    blocks = params["blocks"]
    sd = {"backbone.embeddings.weight": params["tok_emb"],
          "backbone.norm_f.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T,
          "mtp.layers.0.norm.weight": params["final_norm"]["scale"]}
    seen = {"mamba2": 0, "attn": 0, "ffn": 0}
    for layer, kind in enumerate(cfg.layer_kinds):
        pre = f"backbone.layers.{layer}."
        sd[pre + "norm.weight"] = blocks["ln1"]["scale"][layer]
        sub = T.mixer_of(kind)
        mp = jax.tree.map(lambda a: a[seen[sub]], blocks[sub])
        seen[sub] += 1
        at = pre + "mixer."
        if kind == "mamba2":
            for ours, (theirs, matrix) in _MAMBA2_TENSORS.items():
                sd[at + theirs] = mp[ours].T if matrix else mp[ours]
            sd[at + "conv1d.weight"] = mp["conv_w"].T[:, None]
        elif kind == "full":
            for x in "qkvo":
                sd[at + f"{x}_proj.weight"] = mp[f"w{x}"].T
        else:
            sd[at + "gate.weight"] = mp["gate_w"].T
            sd[at + "gate.e_score_correction_bias"] = mp["gate_bias"]
            sd[at + "fc1_latent_proj.weight"] = mp["latent_down"].T
            sd[at + "fc2_latent_proj.weight"] = mp["latent_up"].T
            for ours, theirs in (("w_up", "up_proj"), ("w_down", "down_proj")):
                sd[at + f"shared_experts.{theirs}.weight"] = mp["s" + ours].T
                for e in range(cfg.n_experts):
                    sd[at + f"experts.{cfg.moe_first_expert + e}.{theirs}"
                       ".weight"] = mp[ours][e].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_state_dict_under_the_family_s_names_imports(model):
    """The draft head's tensors (``mtp.*``) are dropped."""
    cfg, params, *_ = model
    hf = next(h for h in MODELS.values()
              if config_from_hf(types.SimpleNamespace(**h)) == cfg)
    got_cfg, got = import_hf_model((_state_dict(cfg, params),
                                    types.SimpleNamespace(**hf)))
    assert got_cfg == cfg
    H.assert_same_tree(params, got)


# --------------------------------------------------------------------------- #
# the experts' activation
# --------------------------------------------------------------------------- #

def test_relu2_s_backward_is_autodiff_s_of_the_plain_form():
    """The held path's activation (``held_expert_act``: row tiles below the
    held pairs' count, a custom backward) against ``jax.grad`` of
    ``square(relu(x))``: value and gradient ``2 relu(x)`` on the live rows,
    nothing read or written behind them."""
    up = jax.random.normal(jax.random.PRNGKey(3), (1024, 48))
    n, tile = jnp.int32(700), 512

    def held(u):
        return jnp.sum(jnp.where(jnp.arange(1024)[:, None] < n,
                                 ML.held_expert_act(u, None, n, "relu2",
                                                    tile), 0.0) ** 2)

    def plain(u):
        return jnp.sum(jnp.where(jnp.arange(1024)[:, None] < n,
                                 jnp.square(jax.nn.relu(u)), 0.0) ** 2)

    np.testing.assert_allclose(held(up), plain(up), rtol=1e-6)
    g_held, g_plain = jax.grad(held)(up), jax.grad(plain)(up)
    np.testing.assert_allclose(g_held[:700], g_plain[:700], rtol=1e-6)
    x = jnp.linspace(-2, 2, 9)
    np.testing.assert_allclose(
        jax.vmap(jax.grad(lambda v: ML._expert_act(v, None, "relu2")))(x),
        2 * jax.nn.relu(x))
    # every place an activation is chosen knows it
    w = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
    xt = jax.random.normal(jax.random.PRNGKey(5), (6, 8))
    np.testing.assert_allclose(
        ML._dense_ffn(xt, w, w.T, None, "relu2"),
        jnp.square(jax.nn.relu(xt @ w)) @ w.T, rtol=1e-6)


def test_a_share_s_gradients_reach_the_latent_and_the_router(cut):
    """``moe_ffn(latent=)`` through its held form, forward and backward,
    against the plain sum over the held pairs (training is no cell of this
    family; the file's one form serves it all the same)."""
    cfg, params, *_ = cut
    lp = jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.hidden_size))

    def system(lp, u):
        return jnp.sum(T._ffn(u, lp, cfg)[0] ** 2)

    def plain(lp, u):
        arch = dict(top_k=cfg.moe_top_k, route_norm=True, route_scale=5.0,
                    first_expert=0, faults=frozenset())
        stack = {k: lp[k][None] for k in ("w_up", "w_down")}
        return jnp.sum(R._experts(u[0], lp, stack, 0, arch)[0] ** 2)

    with jax.default_matmul_precision("highest"):
        g_sys = jax.grad(system)(lp, u)
        g_ref = jax.grad(plain)(lp, u)
    for k in ("latent_down", "latent_up", "gate_w", "w_up", "w_down",
              "sw_up"):
        assert rel(g_sys[k], g_ref[k]) < 1e-4, k


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #
def test_the_tick_s_span_and_gauges_say_which_form_took_which_rows(
        cut, monkeypatch):
    from deepspeed_tpu import telemetry

    cfg, params, toks, _ = cut
    eng = H.engine(FAMILY, cfg, params)
    counter = telemetry.counter("fastgen_ssd_rows_total")
    before = {f: counter.value(form=f) for f in ("step", "chunk")}
    eng.put([1, 2, 3], [toks[0, :20].tolist(), toks[1, :5].tolist(),
                        toks[0, 7:8].tolist()])
    spans = H.spy_on_spans(monkeypatch, "decode_tick")
    eng.step()    # 16 rows: one chunk of the first prompt
    eng.step()    # its last 4 rows, the second prompt whole, the third
    eng.step()    # three decode rows
    assert [s["ssd_step_rows"] for s in spans] == [0, 1, 3]
    assert [s["ssd_chunk_rows"] for s in spans] == [16, 9, 0]
    assert [s["ssd_chunk_pieces"] for s in spans] == [1, 2, 0]
    assert [s["ssd_state_rows"] for s in spans] == [1, 3, 3]
    assert counter.value(form="step") - before["step"] == 4
    assert counter.value(form="chunk") - before["chunk"] == 25
    n = cfg.layer_kinds.count("mamba2")
    per_slot = telemetry.gauge("fastgen_state_bytes_per_slot")
    assert per_slot.value(kind="ssd") == n * 16 * 8 * 128 * 4
    assert per_slot.value(kind="conv") == n * 3 * (128 + 2048) * 4
    assert telemetry.gauge("fastgen_state_bytes").value() == 4 * (
        per_slot.value(kind="ssd") + per_slot.value(kind="conv"))
    eng.flush([1, 2, 3])


def test_a_pool_that_does_not_fit_says_what_takes_what(cut, monkeypatch):
    cfg, params, *_ = cut
    device = jax.devices()[0]
    monkeypatch.setattr(type(device), "memory_stats",
                        lambda self: {"bytes_limit": 1 << 20}, raising=False)
    with pytest.raises(ValueError, match=r"blocks of 4 take .* GB and 3 "
                                         r"sequence slots' state .* GB "
                                         r"beside .* GB of weights"):
        H.engine(FAMILY, cfg, params)


def test_what_the_stack_does_not_write_is_refused_by_name(cut):
    cfg, params, toks, _ = cut
    with pytest.raises(NotImplementedError, match="single sublayers"):
        T.init_params(dataclasses.replace(cfg, first_dense_layers=1),
                      jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="mamba2 layers need"):
        T.init_params(dataclasses.replace(cfg, mamba2_groups=3),
                      jax.random.PRNGKey(0))
    hf = dict(MODELS["cut"])
    for key, value, error in (("attention_bias", True, NotImplementedError),
                              ("hybrid_override_pattern", "MEMEMEMEM*X",
                               ValueError)):
        with pytest.raises(error, match="nemotron_h"):
            config_from_hf(types.SimpleNamespace(**{**hf, key: value}))


# --------------------------------------------------------------------------- #
# the reference's mixer against another implementation
# --------------------------------------------------------------------------- #

def test_the_reference_s_mixer_is_transformers_mamba2():
    """``transformers``' ``Mamba2Mixer.torch_forward`` (installed here) with
    ONE group, where its ungrouped gated norm is the same equation."""
    torch = pytest.importorskip("torch")
    mamba2 = pytest.importorskip("transformers.models.mamba2.modeling_mamba2")
    from transformers import Mamba2Config

    hc = Mamba2Config(hidden_size=32, num_heads=8, head_dim=8, expand=2,
                      n_groups=1, state_size=16, conv_kernel=4, chunk_size=8,
                      use_bias=False, use_conv_bias=True, num_hidden_layers=1,
                      vocab_size=16, rms_norm=True, layer_norm_epsilon=1e-5,
                      time_step_limit=(0.0, float("inf")))
    torch.manual_seed(0)
    mixer = mamba2.Mamba2Mixer(hc, layer_idx=0).eval()
    with torch.no_grad():
        mixer.D.copy_(torch.rand(8) + 0.5)
        mixer.norm.weight.copy_(torch.rand(64) + 0.5)
        u = torch.randn(1, 21, 32)
        want = mixer.torch_forward(u).numpy()[0]
    sd = {k: v.detach().numpy() for k, v in mixer.state_dict().items()}
    lp = {"w_in": sd["in_proj.weight"].T, "wo": sd["out_proj.weight"].T,
          "conv_w": sd["conv1d.weight"][:, 0].T, "conv_b": sd["conv1d.bias"],
          "dt_bias": sd["dt_bias"], "a_log": sd["A_log"],
          "skip_scale": sd["D"], "gate_norm": sd["norm.weight"]}
    arch = dict(m_heads=8, m_dim=8, groups=1, state=16, eps=1e-5,
                faults=frozenset())
    with jax.default_matmul_precision("highest"):
        got = R._mamba2(jnp.asarray(u.numpy()[0]), R._f32(lp), arch)
    assert rel(got, jnp.asarray(want)) < 1e-5

