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
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear_lm as R
from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import (config_from_hf, import_hf_model)
from deepspeed_tpu.ops.pallas import kda as KD
from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

TOL = 2e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
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
#: published pattern cut where a period is not whole, and a share of the
#: experts that does not start at the first
MODELS = {
    "cut": _hf("kkkmkkkm"),
    "remainder": _hf("kkkmkkkmkk"),
    "a-later-share": _hf("kkmk", first_expert=8),
}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _noisy(params, seed=1, std=0.05):
    """Norm gains, the router's bias and every matrix off their start, so
    a dropped one shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([x + std * jax.random.normal(k, x.shape)
                           for x, k in zip(leaves, keys)])


def _build(hf):
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    params = _noisy(T.init_params(cfg, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(0).integers(0, 128, (2, 40)).astype(np.int32)
    return cfg, params, toks


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    hf = MODELS[request.param]
    cfg, params, toks = _build(hf)
    with jax.default_matmul_precision("highest"):
        whole = T.forward(params, jnp.asarray(toks), cfg)
    return cfg, params, toks, whole, R.arch_from_config(hf, hf)


@pytest.fixture(scope="module")
def cut():
    hf = MODELS["cut"]
    return _build(hf) + (R.arch_from_config(hf, hf),)


def _engine(cfg, params, **kw):
    kw = {"n_blocks": 64, "block_size": 4, "max_blocks_per_seq": 16,
          "token_budget": 16, "state_slots": 3, "use_pallas_kernel": False,
          **kw}
    return FastGenEngine(cfg, params, **kw)


def _drive(eng, cfg, toks, attn, chunk, n_prompt, between=None):
    """The runner's check (``benchmarks/runners/serve.py::check_logits``) in
    small: every sequence ``allocate``d once, ticks of the flat prompt rows
    ``chunk`` at a time (sequence and chunk boundaries fall where they
    fall), then decode ticks of one row a sequence; logits of every
    position. ``between(eng)`` runs between two ticks. Returns (logits
    [B, S, V], the sequences' slots)."""
    Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
    S = toks.shape[1]
    tabs, blocks = [], []
    for _ in toks:
        b = eng.allocator.allocate(S // bs + 1)
        t = np.zeros(mb, np.int32)
        t[:len(b)] = b
        tabs.append(t)
        blocks.append(b)
    fwd = jax.jit(lambda pr, pool, t, p, tb: PG.forward_paged(
        pr, t, p, tb, pool, cfg, attention_fn=attn))
    got = {}

    def tick(rows):
        t = np.zeros(Tn, np.int32)
        p = np.zeros(Tn, np.int32)
        tb = np.zeros((Tn, mb), np.int32)
        for r, (i, pos) in enumerate(rows):
            t[r], p[r], tb[r] = toks[i, pos], pos, tabs[i]
        with jax.default_matmul_precision("highest"):
            lg, eng.pool = fwd(eng.params, eng.pool, jnp.asarray(t),
                               jnp.asarray(p), jnp.asarray(tb))
        for r, (i, pos) in enumerate(rows):
            got[(i, pos)] = lg[r]
        if between is not None:
            between(eng)

    flat = [(i, p) for i in range(len(toks)) for p in range(n_prompt)]
    for lo in range(0, len(flat), chunk):
        tick(flat[lo:lo + chunk])
    for p in range(n_prompt, S):
        tick([(i, p) for i in range(len(toks))])
    for b in blocks:
        eng.allocator.free(b)
    return jnp.stack([jnp.stack([got[(i, p)] for p in range(S)])
                      for i in range(len(toks))]), [b[0] for b in blocks]


def test_whole_forward_matches_the_reference(model):
    cfg, params, toks, whole, arch = model
    assert _rel(whole, R.forward_logits(params, toks, arch)) < TOL


@pytest.mark.parametrize("attn,chunk,tol", [
    (None, 13, TOL),          # chunk and sequence boundaries fall mid-tick
    # the kernels (interpret mode) under the tick: ``kda_step`` is exact,
    # the latent kernel multiplies in bfloat16 by design
    (paged_attention, 13, 2e-3),
    (None, 16, TOL),          # a full tick
])
def test_paged_ticks_match_whole_forward_and_reference(model, attn, chunk,
                                                       tol):
    """Chunked prefill of two prompts in one stream of ticks, then decode
    ticks of both sequences: the second sequence starts in the tick that
    ends the first (two runs a tick, the second cut mid-chunk), a decode
    row starts from the state its slot stored, and every store starts full
    of garbage (a run at position 0 must not read its slot's state)."""
    cfg, params, toks, whole, arch = model
    eng = _engine(cfg, params)
    eng.pool = jax.tree.map(lambda x: x + 7.0, eng.pool)
    out, _ = _drive(eng, cfg, toks, attn, chunk, n_prompt=30)
    assert _rel(out, whole) < tol
    assert _rel(out, R.forward_logits(params, toks, arch)) < tol
    assert eng.allocator.free_slots == 3


def test_a_slot_handed_on_starts_from_zero(model):
    """Two sequences, freed, then two others that take the same slots with
    the first pair's state still in them: the logits are the reference's."""
    cfg, params, toks, whole, arch = model
    eng = _engine(cfg, params, state_slots=2)
    _, first = _drive(eng, cfg, toks, None, 13, n_prompt=30)
    others = toks[::-1, ::-1].copy()
    out, second = _drive(eng, cfg, others, None, 11, n_prompt=25)
    assert sorted(first) == sorted(second) == [1, 2]
    assert float(jnp.abs(eng.pool["kda"][:, 1:]).max(axis=(2, 3, 4)).min()) > 0
    assert _rel(out, R.forward_logits(params, others, arch)) < TOL


@pytest.mark.parametrize("fault", ["state-dropped-at-a-tick-boundary",
                                   "conv-inputs-dropped-at-a-tick-boundary",
                                   "state-carried-into-the-next-sequence"])
def test_a_fault_in_the_state_is_seen(fault, monkeypatch):
    """The faults a state a slot invites, made on purpose in the tick: each
    moves the logits by a thousand times the tolerance."""
    cfg, params, toks = _build(MODELS["cut"])
    arch = R.arch_from_config(MODELS["cut"], MODELS["cut"])
    want = R.forward_logits(params, toks, arch)
    eng = _engine(cfg, params)
    between = None
    if fault == "state-carried-into-the-next-sequence":
        eng.pool = jax.tree.map(lambda x: x + 7.0, eng.pool)
        runs_of = HY.runs_of
        monkeypatch.setattr(HY, "runs_of", lambda o, p: runs_of(o, p)._replace(
            fresh=jnp.zeros(o.shape, jnp.bool_)))
    else:
        name = "kda" if fault.startswith("state") else "kda_conv"

        def between(e):
            e.pool = {**e.pool, name: jnp.zeros_like(e.pool[name])}
    out, _ = _drive(eng, cfg, toks, None, 13, n_prompt=30, between=between)
    assert _rel(out, want) > 1000 * TOL


# --------------------------------------------------------------------------- #
# the rule's two forms against the recurrence
# --------------------------------------------------------------------------- #

def _rule_case(slots, positions, fast=False, heads=2, seed=0):
    rng = np.random.default_rng(seed)
    Tn, N, D = len(slots), heads, 128
    slot = jnp.asarray(slots, jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray(positions, jnp.int32))
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, k, v = f(Tn, N, D) / 11, f(Tn, N, D), f(Tn, N, D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(1e-3, 2.0, (Tn, N, D)), jnp.float32)
    if fast:
        # a channel that decays by e^-30 a row: 1 / G overflows float32
        # within three rows of a chunk
        g = g.at[:, 0, 5].set(-30.0)
    b = jnp.asarray(rng.uniform(0, 1, (Tn, N)), jnp.float32)
    state = f(max(slots) + 1, N, D, D)
    s0 = jnp.where(runs.fresh[:, None, None, None], 0.0, state[slot])
    o, after = HY.kda_recurrence(q, k, v, g, b, runs, s0)
    want = np.array(state)
    for t in range(Tn):
        if bool(runs.last[t]) and slots[t] > 0:
            want[slots[t]] = after[t]
    return (q, k, v, g, b, runs, state, slot), \
        jnp.where((slot > 0)[:, None, None], o, 0.0), want


RULE_CASES = {
    # two decode rows, a run that goes on from stored state, a fresh run,
    # two pad rows
    "a-tick-of-16": ([1, 2] + [3] * 5 + [4] * 7 + [0, 0],
                     [9, 4] + list(range(7, 12)) + list(range(7)) + [0, 0],
                     False),
    # runs of 100 and 70 rows (several chunks, cut mid-chunk), decode rows
    # before and after them, a fast channel
    "chunks-and-a-fast-channel": (
        [1] + [3] * 100 + [4] * 70 + [5] + [0] * 3,
        [9] + list(range(7, 107)) + list(range(70)) + [3] + [0] * 3, True),
    "every-row-a-run-of-one": (list(range(1, 9)), [5] * 8, False),
    # a run over three chunks that ends mid-chunk, and a second run that
    # starts in that chunk (two pieces of one chunk), then a decode row
    "two-runs-in-one-chunk": (
        [3] * 150 + [4] * 30 + [5] + [0] * 11,
        list(range(20, 170)) + list(range(30)) + [8] + [0] * 11, False),
    # prompt rows that start off the 64-grid after fewer than 64 decode
    # rows: the run's first piece is the tail of the decode rows' chunk
    "a-run-after-decode-rows": (
        list(range(1, 38)) + [40] * 90 + [0],
        [6] * 37 + list(range(11, 101)) + [0], True),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_both_forms_of_the_rule_match_the_recurrence(case, kernel):
    """``delta_rule`` (runs of one through ``kda_step``, the others through
    ``kda_chunk``: the two Mosaic kernels interpreted where ``kernel``,
    else their plain references) against one row after another: outputs
    and the state each run leaves in its slot."""
    slots, positions, fast = RULE_CASES[case]
    args, o_want, state_want = _rule_case(slots, positions, fast)
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: HY.delta_rule(*a, use_kernel=kernel))(
            *args)
    assert bool(jnp.isfinite(o).all())
    assert _rel(o, o_want) < TOL
    assert _rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("kernel", [False, True])
def test_the_chunk_form_alone_takes_runs_of_one_past_the_step_form_s_count(
        monkeypatch, kernel):
    """More runs of one than the one-row form takes: the rest go through
    the chunk form, a piece a row."""
    monkeypatch.setattr(HY, "KDA_STEP_ROWS", 3)
    args, o_want, state_want = _rule_case(list(range(1, 9)), [5] * 8)
    with jax.default_matmul_precision("highest"):
        o, state = HY.delta_rule(*args, use_kernel=kernel)
    assert _rel(o, o_want) < TOL
    assert _rel(jnp.asarray(state), jnp.asarray(state_want)) < TOL


@pytest.mark.parametrize("kernel", [False, True])
def test_pad_rows_touch_no_state(kernel):
    args, _, _ = _rule_case([0] * 8 + [2] + [0] * 7, [0] * 8 + [3] + [0] * 7)
    o, state = HY.delta_rule(*args, use_kernel=kernel)
    before = args[6]
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(before[0]))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(before[1]))
    assert float(jnp.abs(state[2] - before[2]).max()) > 0
    assert float(jnp.abs(o[:8]).max()) == 0.0


@pytest.mark.parametrize("kernel", [False, True])
def test_a_bucket_whose_chunks_hold_no_row_starts_nothing(kernel):
    """Rows of the one-row form and pads alone: the chunk form has no
    piece, the store is as it was bit for bit and its output zero."""
    slots = list(range(1, 6)) + [0] * 123
    args, _, _ = _rule_case(slots, [4] * 5 + [0] * 123)
    q, k, v, g, b, runs, state, slot = args
    chunk = jax.jit(functools.partial(KD.kda_chunk, interpret=True)) \
        if kernel else KD.kda_chunk_reference
    o, after = chunk(q, k, v, g, b, runs, jnp.zeros((128,), bool), state,
                     slot)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(state))
    assert o.shape == q.shape and float(jnp.abs(o).max()) == 0.0
    n, *_ = KD._pieces(jnp.zeros((128,), bool), runs.start, runs.last,
                       runs.fresh, slot, KD.CHUNK)
    assert int(n[0]) == 0 == KD.count_pieces([])


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("mistake", ["decay-dropped", "b-is-one"])
def test_a_mistake_in_the_rule_is_seen_in_both_forms(mistake, kernel):
    """What the comparison above can see: either form against the
    recurrence that makes a mistake reads far over the tolerance."""
    slots, positions, _ = RULE_CASES["two-runs-in-one-chunk"]
    args, _, _ = _rule_case(slots, positions)
    q, k, v, g, b, runs, state, slot = args
    if mistake == "decay-dropped":
        g = jnp.zeros_like(g)
    else:
        b = jnp.ones_like(b)
    s0 = jnp.where(runs.fresh[:, None, None, None], 0.0, state[slot])
    o_wrong, _ = HY.kda_recurrence(q, k, v, g, b, runs, s0)
    with jax.default_matmul_precision("highest"):
        o, _ = HY.delta_rule(*args, use_kernel=kernel)
    assert _rel(o, jnp.where((slot > 0)[:, None, None], o_wrong, 0.0)) \
        > 100 * TOL


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_host_counts_the_pieces_the_kernel_runs(case):
    """``count_pieces`` (the span's ``kda_chunk_pieces``) is the grid the
    kernel is given, for the runs ``delta_rule`` hands the chunk form."""
    slots, positions, _ = RULE_CASES[case]
    slot = jnp.asarray(slots, jnp.int32)
    runs = HY.runs_of(slot, jnp.asarray(positions, jnp.int32))
    start, last = np.asarray(runs.start), np.asarray(runs.last)
    firsts, ends = np.nonzero(start)[0], np.nonzero(last)[0]
    taken = [(int(a), int(e - a + 1)) for a, e in zip(firsts, ends)
             if slots[a] > 0 and e > a]       # runs of one: the step form
    rows = np.zeros((len(slots),), bool)
    for a, n in taken:
        rows[a:a + n] = True
    T = -(-len(slots) // KD.CHUNK) * KD.CHUNK
    pad = lambda x, fill: jnp.pad(  # noqa: E731
        jnp.asarray(x), (0, T - len(slots)), constant_values=fill)
    n, chunk, lo, hi, _, flag = KD._pieces(
        pad(rows, False), pad(runs.start, True), pad(runs.last, True),
        pad(runs.fresh, True), pad(slot, 0), KD.CHUNK)
    n = int(n[0])
    assert n == KD.count_pieces(taken)
    # a piece lies inside one chunk and one run; a run's first opens it
    # and its last closes it
    assert bool((lo[:n] <= hi[:n]).all()) and bool((hi[:n] < KD.CHUNK).all())
    assert int(jnp.sum(flag[:n] & 1)) == len(taken) \
        == int(jnp.sum((flag[:n] & 4) > 0))
    assert int(jnp.sum(hi[:n] - lo[:n] + 1)) == int(rows.sum())


def test_the_kernel_alone_tool_still_walks():
    """``tools/kda_kernel_alone.py`` on its tiny cases, interpreted: the
    three forms run chained and the kernel agrees with the plain form (its
    times are a chip's to give: none is read here)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        "kda_kernel_alone.py")
    spec = importlib.util.spec_from_file_location("kda_kernel_alone", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert set(tool.CASES) >= {"mixed-one-run", "mixed-two-runs", "decode"}
    forms = tool.forms_of(KD, True)
    assert set(forms) == {"kernel", "plain", "solve"}
    ops, pieces = tool.operands(np.random.default_rng(0),
                                tool.TINY["mixed"], (2, 128))
    assert pieces == 3
    found = tool.compare(KD, ops, True)
    assert found["finite"] and found["o_rel"] < TOL \
        and found["state_rel"] < TOL
    ops, pieces = tool.operands(np.random.default_rng(0),
                                tool.TINY["decode"], (2, 128))
    assert pieces == 0
    for name in ("plain", "solve"):     # the kernel's trace is above
        total, state = tool.chained(forms[name], 2)(*ops)
        assert float(total) == 0.0 or name == "solve"
        np.testing.assert_array_equal(np.asarray(state), np.asarray(ops[7]))


# --------------------------------------------------------------------------- #
# mistakes made on purpose
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("mistake", [
    "decay-dropped", "b-is-one", "taps-reversed", "no-l2norm",
    "rotary-on-latent", "top-7-for-top-8"])
def test_a_mistake_made_on_purpose_is_seen(mistake):
    """Each mistake the cell's notes list, read as ``correct`` would: the
    system against the reference that makes the mistake."""
    hf = _hf("kkkmkkkm", num_experts_per_token=8)
    cfg, params, toks = _build(hf)
    arch = R.arch_from_config(hf, hf)
    if mistake == "top-7-for-top-8":
        arch = {**arch, "top_k": 7}
    else:
        arch = {**arch, "faults": frozenset([mistake])}
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(toks[:1]), cfg)
    assert _rel(got, R.forward_logits(params, toks[:1], arch)) > 100 * TOL


def test_the_shares_add_up():
    """Eight shares of an expert layer (2 of 16 experts each, the shared
    expert counted once) give the reference's uncut layer."""
    hf = _hf("kkmk", num_experts=16, router_experts=16)
    cfg, params, _ = _build(hf)
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
    assert _rel(total, want) < TOL


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
    axes = T.param_logical_axes(cfg)
    flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    flat_a = dict(jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0])
    assert flat_p.keys() == flat_a.keys()
    assert all(len(flat_a[k]) == flat_p[k].ndim for k in flat_p)
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
    flat_w = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    assert flat_w.keys() == flat_g.keys()
    for k in flat_w:
        np.testing.assert_array_equal(np.asarray(flat_w[k]), flat_g[k])


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #

def test_two_sequences_decode_in_one_tick_and_a_slot_is_handed_on(cut):
    """Through ``FastGenEngine.step``: three requests on two slots; the
    third waits, takes the slot of the first to end, and every greedy
    token is the reference's."""
    cfg, params, toks, arch = cut
    eng = _engine(cfg, params, state_slots=2)
    prompts = {1: toks[0, :9].tolist(), 2: toks[1, :30].tolist(),
               3: toks[0, 20:37].tolist()}
    want = {1: 3, 2: 12, 3: 4}
    eng.put(list(prompts), list(prompts.values()))
    slots_seen, both_decoded = {}, False
    with jax.default_matmul_precision("highest"):
        for _ in range(200):
            out = eng.step()
            both_decoded |= {1, 2} <= set(out) and eng.seqs[1].pos > 10
            for u, s in eng.seqs.items():
                if s.blocks:
                    slots_seen[u] = s.blocks[0]
                if not s.done and len(s.generated) >= want[u]:
                    eng._finish(s)
            if all(s.done for s in eng.seqs.values()):
                break
    assert both_decoded
    assert slots_seen[3] == slots_seen[1]     # handed on by the first to end
    for u in (1, 2, 3):
        out = eng.query(u)[1][:want[u]]
        seq = np.asarray(prompts[u] + out, np.int32)[None]
        ref = R.forward_logits(params, seq, arch)[0]
        n = len(prompts[u])
        assert out == [int(t) for t in jnp.argmax(
            ref[n - 1:n - 1 + want[u]], axis=-1)]
    eng.flush([1, 2, 3])
    assert eng.allocator.free_slots == 2 and eng.allocator.free_blocks == 63


def test_the_tick_s_span_and_gauges_say_which_form_took_which_rows(cut):
    from deepspeed_tpu import telemetry

    cfg, params, toks, _ = cut
    eng = _engine(cfg, params)
    spans = []
    real = telemetry.span

    def spy(name, attrs=None, **kw):
        if name == "decode_tick":
            spans.append(attrs)
        return real(name, attrs=attrs, **kw)

    counter = telemetry.counter("fastgen_kda_rows_total")
    before = {f: counter.value(form=f) for f in ("step", "chunk")}
    pieces = telemetry.counter("fastgen_kda_chunk_pieces_total")
    pieces_before = pieces.value()
    eng.put([1, 2, 3], [toks[0, :20].tolist(), toks[1, :5].tolist(),
                        toks[0, 7:8].tolist()])
    import deepspeed_tpu.inference.fastgen as FG
    orig, FG.telemetry.span = FG.telemetry.span, spy
    try:
        eng.step()    # 16 rows: one chunk of the first prompt
        eng.step()    # its last 4 rows, the second prompt whole, the third
        eng.step()    # three decode rows
    finally:
        FG.telemetry.span = orig
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
    eng = FastGenEngine(wide, T.init_params(wide, jax.random.PRNGKey(0)),
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
        _engine(cfg, params)


@pytest.mark.parametrize("entry", ["forward_decode", "pipeline", "tp", "pld"])
def test_entry_points_that_refuse_the_stack(cut, entry):
    cfg, params, toks, _ = cut
    with pytest.raises(NotImplementedError,
                       match="layer kinds|layer_kinds|MLA"):
        if entry == "forward_decode":
            T.forward_decode(params, jnp.asarray(toks[:, :4]), {},
                             jnp.zeros((2,), jnp.int32), cfg)
        elif entry == "pipeline":
            T.pipelined_lm_loss(params, jnp.asarray(toks), cfg, 2)
        elif entry == "pld":
            T.forward_hidden(params, jnp.asarray(toks), cfg,
                             pld_keep=jnp.ones((cfg.num_layers,)))
        else:
            from deepspeed_tpu.comm.mesh import (MeshConfig, initialize_mesh,
                                                 reset_mesh)

            reset_mesh()
            initialize_mesh(MeshConfig(data=4, tensor=2))
            try:
                _engine(cfg, params, tp=True)
            finally:
                reset_mesh()


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
