"""What the family test files (``test_*_stack.py``, ``test_ouro_loop.py``)
share: a family's table (``Family``), its toy models built once, the paged
tick driven as the benchmark's check drives it, and the tests that hold for
every family, written once as functions of a ``Family``.

A family's file fills in a ``Family`` and instantiates the shared tests under
its own names (``test_x = H.x_test(FAMILY, ...)``: pytest names a test by
the module attribute, so node ids stay the file's); what is the family's
alone stays in its file. A plain module, not a plugin: nothing here is
found by name, every use is an import.

Nothing is compiled or evaluated twice in a file: a toy model is built once
a name (``Family.model``), its whole forward and the reference's logits are
kept by model name and token bytes (``whole_forward``,
``reference_logits``), and ``drive`` takes its jitted ``forward_paged`` from
``tick_program``, keyed by what fixes the program.
"""
import contextlib
import dataclasses
import functools
import math
import types
from typing import Any, Callable, Dict, Optional, Tuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.fastgen import FastGenEngine
from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import config_from_hf

TOL = 2e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ENGINE_KW = {"n_blocks": 64, "block_size": 4, "max_blocks_per_seq": 16,
             "token_budget": 16, "state_slots": 3, "use_pallas_kernel": False}
#: a state fault that is no store zeroed between ticks: every run is told
#: it goes on from its slot's state (``HY.runs_of`` patched)
CARRIED = "carried-into-the-next-sequence"


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


_DRAWS = {"normal": jax.random.normal, "uniform": jax.random.uniform}


@functools.lru_cache(maxsize=None)
def _flat_draw(kind, n):
    draw = _DRAWS[kind]
    if kind == "normal":
        return jax.jit(lambda key: draw(key, (n,), jnp.float32))
    return jax.jit(lambda key, lo, hi: draw(key, (n,), jnp.float32, lo, hi))


def _drawn(kind, key, shape, dtype, *bounds):
    """``jax.random.<kind>(key, shape)``, bit for bit, out of ONE program a
    power of two of elements: under ``jax_threefry_partitionable`` (set in
    ``conftest.py``) an element's bits are a function of the key and of its
    flat index alone, so a shape's draw is the head of a longer flat one."""
    assert jax.config.jax_threefry_partitionable and dtype == jnp.float32
    size = math.prod(shape)
    n = max(4096, 1 << max(size - 1, 0).bit_length())
    flat = _flat_draw(kind, n)(key, *[np.float32(b) for b in bounds])
    return np.asarray(flat)[:size].reshape(shape).astype(dtype)


@contextlib.contextmanager
def drawn_whole(on_host=False):
    """While a toy model's parameters are built eagerly,
    ``jax.random.normal`` / ``uniform`` hand out the same float32 numbers
    without a compile a shape (a second of XLA's time each, fifty leaves a
    model). ``on_host``: as numpy arrays, so that what is then done to them
    (``* std``) is numpy's float32 arithmetic, which is the eager
    operation's, and compiles nothing either."""
    put = (lambda x: x) if on_host else jnp.asarray

    def normal(key, shape=(), dtype=jnp.float32):
        return put(_drawn("normal", key, tuple(shape), dtype))

    def uniform(key, shape=(), dtype=jnp.float32, minval=0.0, maxval=1.0):
        return put(_drawn("uniform", key, tuple(shape), dtype, minval,
                          maxval))

    with mock.patch.object(jax.random, "normal", normal), \
            mock.patch.object(jax.random, "uniform", uniform):
        yield


def noisy(params, seed=1, std=0.05):
    """Norm gains, biases and every matrix off their start, so a dropped
    one shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    with drawn_whole(on_host=True):
        return tree.unflatten([
            jnp.asarray(np.asarray(x) + np.float32(std) * jax.random.normal(
                k, x.shape)) for x, k in zip(leaves, keys)])


def init_params(cfg, key):
    """``T.init_params``, its draws made whole (``drawn_whole``)."""
    with drawn_whole(on_host=True):
        return jax.tree.map(jnp.asarray, T.init_params(cfg, key))


def build(hf, tokens=(2, 40), noise=noisy, configure=None):
    """(config, parameters off their start, tokens) of HF keys ``hf``."""
    cfg = config_from_hf(types.SimpleNamespace(**hf))
    if configure is not None:
        cfg = configure(cfg)
    params = noise(init_params(cfg, jax.random.PRNGKey(0)))
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, tokens).astype(np.int32)
    return cfg, params, toks


@dataclasses.dataclass
class Model:
    name: str
    hf: Dict[str, Any]
    cfg: Any
    params: Any
    toks: np.ndarray
    arch: Dict[str, Any]

    @functools.cached_property
    def on_host(self):
        """``params`` as numpy arrays, for the reference: it takes a layer's
        leaves by ``a[layer]``, which on a device array is a compile a
        leaf's shape and a dispatch a leaf a layer."""
        return jax.tree.map(np.asarray, self.params)


@dataclasses.dataclass
class Family:
    """A family's table. ``reference``: its ``benchmarks.reference`` module;
    ``models``: HF keys of each named toy model; ``engine_kw`` over
    ``ENGINE_KW``; ``noise`` / ``configure``: where its parameters or its
    config are not ``build``'s."""
    reference: Any
    models: Dict[str, Dict[str, Any]]
    tol: float = TOL
    tokens: Tuple[int, int] = (2, 40)
    engine_kw: Dict[str, Any] = dataclasses.field(default_factory=dict)
    noise: Callable = noisy
    configure: Optional[Callable] = None
    _memo: Dict[Any, Any] = dataclasses.field(default_factory=dict)

    def model(self, name) -> Model:
        key = ("model", name)
        if key not in self._memo:
            hf = self.models[name]
            self._memo[key] = Model(
                name, hf, *build(hf, self.tokens, self.noise, self.configure),
                self.reference.arch_from_config(hf, hf))
        return self._memo[key]


def whole_forward(family, model):
    """``T.forward`` of the model's tokens, once a model."""
    key = ("whole", model.name)
    if key not in family._memo:
        with jax.default_matmul_precision("highest"):
            family._memo[key] = T.forward(
                model.params, jnp.asarray(model.toks), model.cfg)
    return family._memo[key]


def reference_logits(family, model, toks=None, arch=None):
    """The reference's logits of ``toks`` (the model's own by default) under
    ``arch`` (the model's own by default), once a (model, tokens, arch)."""
    toks = model.toks if toks is None else np.asarray(toks)
    arch = model.arch if arch is None else arch
    key = ("reference", model.name, toks.shape, toks.tobytes(), repr(arch))
    if key not in family._memo:
        family._memo[key] = family.reference.forward_logits(
            model.on_host, toks, arch)
    return family._memo[key]


def engine(family, cfg, params, **kw):
    return FastGenEngine(cfg, params,
                         **{**ENGINE_KW, **family.engine_kw, **kw})


_TICKS: Dict[Any, Callable] = {}


def tick_program(cfg, attn, Tn, mb, patched=None):
    """The jitted ``forward_paged`` of a configuration, an ``attention_fn``
    and a tick's shape: compiled once, whoever drives it. ``patched`` names
    what a caller has monkeypatched under the trace, so that its program is
    no one else's."""
    key = (cfg, attn, Tn, mb, patched)
    if key not in _TICKS:
        _TICKS[key] = jax.jit(lambda pr, pool, t, p, tb: PG.forward_paged(
            pr, t, p, tb, pool, cfg, attention_fn=attn))
    return _TICKS[key]


def drive(eng, toks, attn, chunk, n_prompt, between=None, lens=None,
          patched=None):
    """The runner's check (``benchmarks/runners/serve.py::check_logits``) in
    small: every sequence ``allocate``d once, ticks of the flat prompt rows
    ``chunk`` at a time (sequence and chunk boundaries fall where they
    fall; a tick's other rows are pads), then decode ticks of one row a
    live sequence; logits of every position. ``between(eng)`` runs between
    two ticks; ``lens`` cuts the sequences to unequal lengths. Returns
    (logits ``[B, S, V]``, or a list of ``[n, V]`` under ``lens``; the
    sequences' blocks, freed)."""
    Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
    ns = [toks.shape[1]] * len(toks) if lens is None else list(lens)
    tabs, blocks = [], []
    for n in ns:
        b = eng.allocator.allocate(n // bs + 1)
        t = np.zeros(mb, np.int32)
        t[:len(b)] = b
        tabs.append(t)
        blocks.append(b)
    fwd = tick_program(eng.cfg, attn, Tn, mb, patched)
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
        lg = np.asarray(lg)     # (a row read by ``lg[r]`` is a compile)
        for r, (i, pos) in enumerate(rows):
            got[(i, pos)] = lg[r]
        if between is not None:
            between(eng)

    flat = [(i, p) for i, n in enumerate(ns) for p in range(min(n_prompt, n))]
    for lo in range(0, len(flat), chunk):
        tick(flat[lo:lo + chunk])
    for p in range(n_prompt, max(ns)):
        tick([(i, p) for i, n in enumerate(ns) if p < n])
    for b in blocks:
        eng.allocator.free(b)
    out = [jnp.asarray(np.stack([got[(i, p)] for p in range(n)]))
           for i, n in enumerate(ns)]
    return (jnp.stack(out) if lens is None else out), blocks


def serve_greedy(eng, prompts, want, ticks=200):
    """``put`` the prompts and ``step()`` until every uid has ``want[uid]``
    tokens, finishing each at its count. Returns ({uid: the slot (first
    block) it held}, whether uids 1 and 2 decoded in one tick)."""
    eng.put(list(prompts), list(prompts.values()))
    slots_seen, both_decoded = {}, False
    with jax.default_matmul_precision("highest"):
        for _ in range(ticks):
            out = eng.step()
            both_decoded |= {1, 2} <= set(out) and eng.seqs[1].pos > 10
            for u, s in eng.seqs.items():
                if s.blocks:
                    slots_seen[u] = s.blocks[0]
                if not s.done and len(s.generated) >= want[u]:
                    eng._finish(s)
            if all(s.done for s in eng.seqs.values()):
                break
    return slots_seen, both_decoded


def assert_greedy_tokens_are_the_reference_s(family, model, eng, prompts,
                                             want):
    """Every token the engine kept is the argmax of the reference's logits,
    teacher-forced on the engine's own output."""
    for u, prompt in prompts.items():
        out = eng.query(u)[1][:want[u]]
        seq = np.asarray(prompt + out, np.int32)[None]
        ref = family.reference.forward_logits(model.on_host, seq,
                                              model.arch)[0]
        n = len(prompt)
        assert out == [int(t) for t in jnp.argmax(
            ref[n - 1:n - 1 + want[u]], axis=-1)]


def assert_same_tree(want, got):
    """Two trees of arrays: the same leaves under the same paths, bit for
    bit."""
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    assert flat_w.keys() == flat_g.keys()
    for k in flat_w:
        np.testing.assert_array_equal(np.asarray(flat_w[k]), flat_g[k])


def assert_axes_name_every_leaf(cfg, params):
    """``T.param_logical_axes``: an axis name a dimension of every leaf."""
    flat_p = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    flat_a = dict(jax.tree_util.tree_flatten_with_path(
        T.param_logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0])
    assert flat_p.keys() == flat_a.keys()
    assert all(len(flat_a[k]) == flat_p[k].ndim for k in flat_p)


def spy_on_spans(monkeypatch, name):
    """The attributes of every ``name`` span ``fastgen`` opens from here on."""
    import deepspeed_tpu.inference.fastgen as FG

    spans, real = [], FG.telemetry.span

    def spy(span_name, attrs=None, **kw):
        if span_name == name:
            spans.append(attrs)
        return real(span_name, attrs=attrs, **kw)

    monkeypatch.setattr(FG.telemetry, "span", spy)
    return spans


def load_tool(name):
    """``tools/<name>.py`` as a module."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "tools",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# --------------------------------------------------------------------------- #
# the tests every family shares, each a function of its table
# --------------------------------------------------------------------------- #

def whole_forward_test(family, models):
    @pytest.mark.parametrize("name", models)
    def test(name):
        m = family.model(name)
        assert rel(whole_forward(family, m),
                   reference_logits(family, m)) < family.tol
    return test


def paged_ticks_test(family, models, cases, n_prompt, garbage=7.0,
                     free_slots=3, also=None):
    """``cases``: (attention_fn, chunk, tolerance, the engine's keywords).
    Chunked prefill of two prompts in one stream of ticks, then decode
    ticks of both: against the whole forward and the reference, every store
    full of ``garbage`` at the start. ``also(eng)``: the family's own
    assertions on the engine afterwards."""
    @pytest.mark.parametrize("attn,chunk,tol,kw", cases, ids=[
        "-".join([getattr(a, "__name__", "None"), str(c)]
                 + [str(v) for v in kw.values()]) for a, c, _, kw in cases])
    @pytest.mark.parametrize("name", models)
    def test(name, attn, chunk, tol, kw):
        m = family.model(name)
        eng = engine(family, m.cfg, m.params, **kw)
        if garbage is not None:
            eng.pool = jax.tree.map(lambda x: x + garbage, eng.pool)
        out, _ = drive(eng, m.toks, attn, chunk, n_prompt)
        assert rel(out, whole_forward(family, m)) < tol
        assert rel(out, reference_logits(family, m)) < tol
        if free_slots is not None:
            assert eng.allocator.free_slots == free_slots
        if also is not None:
            also(eng)
    return test


def slot_handed_on_test(family, models, stores_hold_the_first_pair):
    """Two sequences, freed, then two others that take the same slots with
    the first pair's state still in them (``stores_hold_the_first_pair(eng)``
    asserts that): the logits are the reference's."""
    @pytest.mark.parametrize("name", models)
    def test(name):
        m = family.model(name)
        eng = engine(family, m.cfg, m.params, state_slots=2)
        _, first = drive(eng, m.toks, None, 13, n_prompt=30)
        others = m.toks[::-1, ::-1].copy()
        out, second = drive(eng, others, None, 11, n_prompt=25)
        assert sorted(b[0] for b in first) == sorted(b[0] for b in second) \
            == [1, 2]
        stores_hold_the_first_pair(eng)
        assert rel(out, reference_logits(family, m, others)) < family.tol
    return test


def state_fault_test(family, model, faults, times):
    """``faults``: name -> the store zeroed between two ticks, or
    ``CARRIED``. The faults a state a slot invites, made on purpose in the
    tick: each moves the logits by over ``times`` the tolerance."""
    @pytest.mark.parametrize("fault", list(faults))
    def test(fault, monkeypatch):
        m = family.model(model)
        eng = engine(family, m.cfg, m.params)
        between, patched = None, None
        if faults[fault] == CARRIED:
            eng.pool = jax.tree.map(lambda x: x + 7.0, eng.pool)
            runs_of, patched = HY.runs_of, CARRIED
            monkeypatch.setattr(HY, "runs_of", lambda o, p: runs_of(
                o, p)._replace(fresh=jnp.zeros(o.shape, jnp.bool_)))
        else:
            store = faults[fault]

            def between(e):
                e.pool = {**e.pool, store: jnp.zeros_like(e.pool[store])}
        out, _ = drive(eng, m.toks, None, 13, n_prompt=30, between=between,
                       patched=patched)
        assert rel(out, reference_logits(family, m)) > times * family.tol
    return test


def reference_mistake_test(family, model, mistakes, seen, sequences=None,
                           and_the_right_one=False):
    """Each mistake read as ``correct`` would: the system (the model's whole
    forward, of its first ``sequences``) against the reference that makes
    it. ``mistakes``: name -> what the mistake changes of the reference's
    ``arch``; ``seen(name)``: how far apart they must stand."""
    @pytest.mark.parametrize("mistake", list(mistakes))
    def test(mistake):
        m = family.model(model)
        whole, toks = whole_forward(family, m)[:sequences], \
            m.toks[:sequences]
        wrong = reference_logits(family, m, toks,
                                 {**m.arch, **mistakes[mistake]})
        assert rel(whole, wrong) > seen(mistake)
        if and_the_right_one:
            assert rel(whole, reference_logits(family, m, toks)) < family.tol
    return test


def program_mistake_test(family, model, names, mistakes,
                         toks_of=lambda t: t[:1]):
    """Each fault of ``names`` made in the program's config or its
    parameters (``mistakes(cfg, params)``: name -> (config fields,
    parameters)) moves the logits by far more than the tolerance off the
    reference's."""
    @pytest.mark.parametrize("mistake", names)
    def test(mistake):
        m = family.model(model)
        toks = toks_of(m.toks)
        wrong, p = mistakes(m.cfg, m.params)[mistake]
        with jax.default_matmul_precision("highest"):
            got = T.forward(p, jnp.asarray(toks),
                            dataclasses.replace(m.cfg, **wrong))
        assert rel(got, reference_logits(family, m, toks)) \
            > 100 * family.tol
    return test


def two_sequences_test(family, model, both_decode=True):
    """Through ``FastGenEngine.step``: three requests on two slots; the
    third waits, takes the slot of the first to end, and every greedy token
    is the reference's."""
    def test():
        m = family.model(model)
        eng = engine(family, m.cfg, m.params, state_slots=2)
        prompts = {1: m.toks[0, :9].tolist(), 2: m.toks[1, :30].tolist(),
                   3: m.toks[0, 20:37].tolist()}
        want = {1: 3, 2: 12, 3: 4}
        slots_seen, both_decoded = serve_greedy(eng, prompts, want)
        assert both_decoded or not both_decode
        assert slots_seen[3] == slots_seen[1]   # handed on by the first to end
        assert_greedy_tokens_are_the_reference_s(family, m, eng, prompts,
                                                 want)
        eng.flush([1, 2, 3])
        assert eng.allocator.free_slots == 2 \
            and eng.allocator.free_blocks == 63
    return test


def entry_points_refuse_test(family, models, match="layer kinds|layer_kinds"):
    """What assumes one homogeneous stack says so in a sentence: the slot
    cache's decode, the pipeline, progressive layer drop and a tensor axis
    under the engine."""
    @pytest.mark.parametrize("entry", ["forward_decode", "pipeline", "tp",
                                       "pld"])
    @pytest.mark.parametrize("name", models)
    def test(name, entry):
        m = family.model(name)
        cfg, params, toks = m.cfg, m.params, jnp.asarray(m.toks)
        with pytest.raises(NotImplementedError, match=match):
            if entry == "forward_decode":
                T.forward_decode(params, toks[:, :4], {},
                                 jnp.zeros((2,), jnp.int32), cfg)
            elif entry == "pipeline":
                T.pipelined_lm_loss(params, toks, cfg, 2)
            elif entry == "pld":
                T.forward_hidden(params, toks, cfg,
                                 pld_keep=jnp.ones((cfg.num_layers,)))
            else:
                from deepspeed_tpu.comm.mesh import (MeshConfig,
                                                     initialize_mesh,
                                                     reset_mesh)

                reset_mesh()
                initialize_mesh(MeshConfig(data=4, tensor=2))
                try:
                    engine(family, cfg, params, tp=True)
                finally:
                    reset_mesh()
    return test
