"""A LOOPED stack (``TransformerConfig.loop_passes``; the ``ouro`` family,
Ouro-2.6B): the stack's layers run R times over the SAME leaves, a cache
layer a (pass, layer), the final norm after every pass, and an exit gate
that chooses, a token at a time, the pass whose state feeds the head.

Toy widths (3 layers of 4 heads of 16 under a hidden of 64), float32,
matmul precision "highest": the paged tick (``models/paged.forward_paged``
over the engine's blocks), the whole-sequence forward (``T.forward``) and
the plain reference (``benchmarks/reference/ouro_lm.py``, which imports
nothing of the program) are three implementations of the same equations and
agree to rounding, ~1e-6 relative; the tolerance 2e-5 leaves room for the
order of float32 sums and none for a pass too few, a norm left out or made
twice, another pass's cache, dropped or misplaced post-norms, the wrong pass
at the head or a gate without its bias: every fault made on purpose below
reads over a hundred times the tolerance.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmarks.reference import ouro_lm as R
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf, import_hf_model
from family_harness import CATALOG, TOL, rel

CONFIG = "benchmarks/configs/ouro-2.6b.json"
L, V = 3, 128


def _hf(passes: int, threshold: float = 1.0, **kw):
    hf = dict(
        model_type="ouro", hidden_size=64, head_dim=16,
        num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=L,
        intermediate_size=96, hidden_act="silu", rms_norm_eps=1e-6,
        rope_theta=1000000, rope_scaling=None, tie_word_embeddings=False,
        vocab_size=V, max_position_embeddings=512, total_ut_steps=passes,
        early_exit_threshold=threshold, sliding_window=None,
        use_sliding_window=False)
    hf.update(kw)
    return hf


def _off_their_start(params):
    """Norm gains, the gate's bias and every matrix off their start, so a
    dropped one shows (a norm made twice is the identity at gains of 1)."""
    params = H.noisy(params, std=0.1)
    if "exit_gate" in params:
        params["exit_gate"]["b"] = params["exit_gate"]["b"] + 0.4
    return params


#: one, two and four passes under a threshold that divides the rows, and an
#: unlooped stack as published
FAMILY = H.Family(
    R, {"1-pass": _hf(1, 0.6), "2-passes": _hf(2, 0.6),
        "4-passes": _hf(4, 0.6), "unlooped": _hf(1)},
    noise=_off_their_start,
    configure=lambda cfg: dataclasses.replace(cfg, init_std=0.2),
    engine_kw={"n_blocks": 33, "max_blocks_per_seq": 12, "state_slots": None})
PASSES = ["1-pass", "2-passes", "4-passes"]


@pytest.fixture(scope="module")
def looped():
    m = FAMILY.model("4-passes")
    return m.cfg, m.params, m.toks, m.arch


# --------------------------------------------------------------------------- #
# three implementations of the same equations
# --------------------------------------------------------------------------- #

test_whole_forward_matches_the_reference = H.whole_forward_test(
    FAMILY, PASSES)
test_a_mistake_made_on_purpose_is_seen = H.reference_mistake_test(
    FAMILY, "4-passes", seen=lambda mistake: 100 * TOL,
    mistakes={m: {"faults": frozenset([m])} for m in R.FAULTS})


@pytest.mark.parametrize("chunk", [16, 7])
@pytest.mark.parametrize("name", PASSES)
def test_paged_ticks_match_the_reference(name, chunk):
    """Chunked prefill, then decode through the pool: prompts split across
    ticks, pad rows, two sequences of unequal length."""
    m = FAMILY.model(name)
    lens = (40, 23)
    got, _ = H.drive(H.engine(FAMILY, m.cfg, m.params), m.toks, None, chunk,
                     18, lens=lens)
    for i, n in enumerate(lens):
        want = H.reference_logits(FAMILY, m, m.toks[i:i + 1, :n])[0]
        assert rel(got[i], want) < TOL


def test_the_pool_has_a_cache_layer_a_pass_and_layer(looped):
    """``R x L`` layers; after a tick, cache layer ``t * L + l`` holds pass
    t's keys and values of layer l (the reference's, one application after
    another) and no other pass's."""
    cfg, params, toks, arch = looped
    eng = H.engine(FAMILY, cfg, params)
    assert eng.pool["k"].shape == (4 * L, 33, 4, 4, 16)
    n = 14
    _, blocks = H.drive(eng, toks, None, 16, n, lens=(n,))
    tabs = [np.asarray(b) for b in blocks]
    arch = R._Frozen(arch)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["tok_emb"])[toks[0, :n]]
        for t in range(4):
            for l in range(L):
                lp = jax.tree.map(lambda a: a[l], params["blocks"])
                x, k, v = R._layer(x, lp, None, arch=arch)
                for name, want in (("k", k), ("v", v)):
                    held = eng.pool[name][t * L + l][tabs[0][:4]].reshape(
                        16, 4, 16)[:n]
                    assert rel(held, want) < TOL, (t, l, name)
            x = R._rms_norm(x, params["final_norm"]["scale"], arch["eps"])
    # the passes' keys differ: a layer shared by the passes would not pass
    assert rel(eng.pool["k"][L][tabs[0][0]], eng.pool["k"][0][tabs[0][0]]) \
        > 0.1


@pytest.mark.parametrize("threshold", [1.0, 0.6, 0.0])
def test_a_threshold_chooses_a_pass_a_row(looped, threshold):
    """The exit rule on one tick's rows, at the published threshold (the
    last pass for every row), one that divides the rows and 0 (pass 0):
    the program's choice is the reference's and so are the logits."""
    cfg, params, toks, arch = looped
    cfg = dataclasses.replace(cfg, exit_threshold=threshold)
    arch = {**arch, "threshold": threshold}
    n = 16
    pool = PG.init_paged_kv(cfg, 9, 4)
    table = np.zeros((n, 12), np.int32)
    table[:, :4] = [1, 2, 3, 4]
    with jax.default_matmul_precision("highest"):
        x, _, _ = PG.forward_hidden(
            params, jnp.asarray(toks[0, :n]), jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(table), pool, cfg)
        assert x.shape == (n, 4, 64)
        logits, pdf = PG.head_logits(params, x, cfg, with_exit=True)
    want = R.forward(params, toks[:1, :n], arch)
    chosen = np.asarray(T.chosen_pass(pdf, threshold))
    np.testing.assert_array_equal(chosen, np.asarray(want["chosen"][0]))
    np.testing.assert_allclose(np.asarray(pdf), want["exit_pdf"][0],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(pdf).sum(-1), 1.0, atol=1e-6)
    assert rel(logits, want["logits"][0]) < TOL
    assert set(chosen) == {1.0: {3}, 0.0: {0}}.get(threshold, set(chosen))
    if threshold == 0.6:
        assert len(set(chosen)) >= 3       # different passes in one tick


# --------------------------------------------------------------------------- #
# the published shape
# --------------------------------------------------------------------------- #

def _published():
    with open(CONFIG) as f:
        conf = json.load(f)
    keys = {k: v for k, v in conf.items() if not isinstance(v, (dict, list))
            or k == "layer_types"}
    return conf, config_from_hf(types.SimpleNamespace(**keys))


def test_the_published_config_counts_its_parameters():
    conf, cfg = _published()
    assert (cfg.num_layers, cfg.loop_passes, cfg.exit_threshold) \
        == (48, 4, 1.0)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_size, cfg.vocab_size, cfg.max_seq_len) \
        == (2048, 16, 16, 128, 5632, 49152, 65536)
    assert cfg.post_norms and not cfg.tie_embeddings \
        and cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert cfg.num_params() == 2_667_974_657 \
        == sum(x.size for x in jax.tree.leaves(shapes)) \
        == conf["bytes"]["num_params_as_run"]
    layer = sum(x.size // 48 for x in jax.tree.leaves(shapes["blocks"]))
    assert layer == conf["bytes"]["layer"] == 51_388_416
    # the pool: 192 cache layers of 8,192 B a token
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        dataclasses.replace(cfg, dtype="bfloat16"), 193, 32))
    assert pool["k"].shape == (192, 193, 32, 16, 128)
    held = dict((s.name, n) for s, n in PG.store_bytes(cfg, pool))
    assert sum(held.values()) // (193 * 32) == 1_572_864 \
        == conf["bytes"]["kv_bytes_a_token"] == 192 * 8192
    assert same_axes(T.param_logical_axes(cfg), shapes)


def same_axes(axes, shapes) -> bool:
    flat_a = dict(jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0])
    flat_s = dict(jax.tree_util.tree_flatten_with_path(shapes)[0])
    return flat_a.keys() == flat_s.keys() and all(
        len(flat_a[k]) == flat_s[k].ndim for k in flat_a)


def test_the_catalog_s_keys_are_the_file_s():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    conf, _ = _published()
    assert conf["source"] == row["source_url"] and conf["reduced"] == []
    for key, value in row["config"].items():
        assert conf[key] == value, key
    assert conf["published"] == conf["as_run"]["serve"]


def _state_dict(cfg, params):
    """``params`` under the family's tensor names (``nn.Linear`` weights
    ``[out, in]``)."""
    sd = {"model.embed_tokens.weight": params["tok_emb"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T,
          "model.early_exit_gate.weight": params["exit_gate"]["w"].T,
          "model.early_exit_gate.bias": params["exit_gate"]["b"]}
    names = {"ln1": "input_layernorm", "ln1_post": "input_layernorm_2",
             "ln2": "post_attention_layernorm",
             "ln2_post": "post_attention_layernorm_2"}
    mats = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
            "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
            "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
            "w_down": "mlp.down_proj"}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for ours, theirs in names.items():
            sd[pre + theirs + ".weight"] = params["blocks"][ours]["scale"][i]
        for ours, theirs in mats.items():
            sd[pre + theirs + ".weight"] = params["blocks"][ours][i].T
    return {k: np.asarray(v) for k, v in sd.items()}


def test_state_dict_under_the_family_s_names_imports(looped):
    cfg, params, *_ = looped
    hf = _hf(4, 0.6)
    got_cfg, got = import_hf_model((_state_dict(cfg, params),
                                    types.SimpleNamespace(**hf)))
    assert got_cfg == dataclasses.replace(cfg, init_std=got_cfg.init_std)
    H.assert_same_tree(params, got)


# --------------------------------------------------------------------------- #
# one set of leaves
# --------------------------------------------------------------------------- #

def test_the_layers_leaves_appear_once_in_the_tick(looped):
    """The tick as traced: R scans, each fed the parameters' OWN leaves (the
    program's inputs, no copy or slice of them a pass). What the compiled
    program holds at the cell's size is ``test_chip_compile.py``'s."""
    cfg, params, *_ = looped
    eng = H.engine(FAMILY, cfg, params, n_blocks=9)
    packed = eng._pack_tick(np.zeros(16, np.int32), np.zeros(16, np.int32),
                            np.zeros((16, 12), np.int32),
                            np.zeros(2, np.uint32))
    tick = eng._build_tick(16, 12)
    jaxpr = jax.make_jaxpr(lambda *a: tick(*a))(eng.params, eng.pool,
                                                jnp.asarray(packed))
    inner = next(e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name in ("jit", "pjit")).params["jaxpr"].jaxpr
    n_leaves = len(jax.tree.leaves(params))
    blocks = len(jax.tree.leaves(params["blocks"]))
    scans = [e for e in inner.eqns if e.primitive.name == "scan"]
    assert len(scans) == 4
    inputs = set(map(id, inner.invars[:n_leaves]))
    for scan in scans:
        xs = scan.invars[scan.params["num_consts"]
                         + scan.params["num_carry"]:]
        assert len(xs) == blocks and all(id(v) in inputs for v in xs)
    assert len({tuple(map(id, s.invars[s.params["num_consts"]
                                       + s.params["num_carry"]:]))
                for s in scans}) == 1


# --------------------------------------------------------------------------- #
# what a loop is not yet
# --------------------------------------------------------------------------- #

def test_what_assumes_one_application_a_layer_refuses_by_name(looped):
    cfg, params, toks, _ = looped
    toks = jnp.asarray(toks)
    with pytest.raises(NotImplementedError, match="forward_decode"):
        T.forward_decode(params, toks[:, :1], T.init_kv_cache(cfg, 2, 8),
                         jnp.zeros((2,), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="pipeline schedule"):
        T.pipelined_lm_loss(params, toks, cfg, None, 2)
    for what, kw in (("progressive layer drop",
                      {"pld_keep": jnp.ones((L,))}),
                     ("random-LTD", {"random_ltd_idx": jnp.arange(8)})):
        with pytest.raises(NotImplementedError, match=what):
            T.forward_hidden(params, toks, cfg, **kw)
    with pytest.raises(NotImplementedError, match="scan_chunks"):
        T.forward_hidden(params, toks,
                         dataclasses.replace(cfg, scan_chunks=2))
    for kw in ({"n_experts": 4}, {"layer_kinds": ("full",) * L},
               {"first_dense_layers": 1, "n_experts": 4}):
        with pytest.raises(NotImplementedError, match="looped stack"):
            T.init_params(dataclasses.replace(cfg, **kw),
                          jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError, match="looped stack"):
            PG.cache_kinds(dataclasses.replace(cfg, **kw))
    with pytest.raises(ValueError, match="exit_threshold"):
        T.init_params(dataclasses.replace(cfg, exit_threshold=1.5),
                      jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ouro"):
        config_from_hf(types.SimpleNamespace(
            **_hf(4, rope_scaling={"type": "linear", "factor": 2.0})))


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def test_the_engine_s_tokens_spans_counters_and_gauge(monkeypatch):
    """Through ``FastGenEngine.step``: two requests of unequal length, a
    prompt split across ticks; every greedy token is the reference's; the
    ``decode_tick`` span says the passes and the cache layers; the exit
    mass of the rows whose token was read sums to their count."""
    m = FAMILY.model("4-passes")
    eng = H.engine(FAMILY, m.cfg, m.params)
    assert telemetry.gauge("fastgen_cache_layers").value() == 4 * L
    prompts = {1: m.toks[0, :21].tolist(), 2: m.toks[1, :6].tolist()}
    want = {1: 5, 2: 9}
    apps = telemetry.counter("fastgen_layer_applications_total")
    mass = telemetry.counter("fastgen_exit_mass_total")
    gen = telemetry.counter("fastgen_generated_tokens_total")
    before = (apps.total(), mass.total(), gen.total(),
              [mass.value(**{"pass": str(t)}) for t in range(4)])
    spans = H.spy_on_spans(monkeypatch, "decode_tick")
    H.serve_greedy(eng, prompts, want, ticks=40)
    H.assert_greedy_tokens_are_the_reference_s(FAMILY, m, eng, prompts, want)
    assert spans and all(s["loop_passes"] == 4 and s["cache_layers"] == 4 * L
                         for s in spans)
    assert apps.total() - before[0] == len(spans) * 4 * L
    # a row's exit distribution sums to one: the mass is the tokens read
    read = gen.total() - before[2]
    assert abs(mass.total() - before[1] - read) < 1e-4 * read
    by_pass = [mass.value(**{"pass": str(t)}) - b
               for t, b in enumerate(before[3])]
    assert all(m > 0 for m in by_pass)
    eng.flush([1, 2])
    assert eng.allocator.free_blocks == 32


def test_an_unlooped_engine_says_nothing_of_passes():
    m = FAMILY.model("unlooped")
    params = m.params
    eng = H.engine(FAMILY, m.cfg, params)
    assert eng._loop_attrs == {} and "exit_gate" not in params
    assert eng.pool["k"].shape[0] == L
