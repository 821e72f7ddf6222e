"""``T.scan_periods`` over stacks whose runs are CUTS of their stacked
leaves: a run of several steps reads the whole leaf in place, a period's
layers at ``ahead + step * per`` inside the loop's body; what a step
receives is what a plain Python loop takes by ``a[i]``, whichever way a
leaf reached it (the scan's operand or the body's own slice).

Toy leaves: a layer's ``ln`` and ``w_gate`` stacked by LAYER, its mixer's
``w`` and ``b`` stacked by MIXER, in one tree.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer as T

_KINDS = {"m": "mamba2", "f": "full", "c": "conv"}
#: letters a layer -> the runs ``T.kind_runs`` makes of them
STACKS = {
    # granite-4.0-h-small's cut: a period of six once, then FOUR steps of
    # one layer out of the middle of the by-layer and the mamba2 leaves
    "mmmmmfmmmm": [(0, "mmmmmf", 1), (6, "m", 4)],
    # two steps that are part of the by-layer and the conv leaves and the
    # WHOLE of the attention leaves, then a tail past the last whole period
    "ccfcccfccc": [(0, "ccfc", 2), (8, "c", 2)],
    # a tail of two steps of a period of TWO, cut out of three mixers' leaves
    "cfcfmcfcf": [(0, "cfcfm", 1), (5, "cf", 2)],
}


def _blocks(kinds, by_mixer: bool, key=None):
    """Stacked leaves of a stack of ``kinds``: whole numbers that tell the
    layers apart (``key`` None), or random floats."""
    def leaf(n, shape, at):
        if key is None:
            return (at + jnp.arange(n * int(np.prod(shape)), dtype=jnp.float32)
                    ).reshape((n,) + shape) % 7 + 1
        return jax.random.normal(jax.random.fold_in(key, at), (n,) + shape)

    L = len(kinds)
    blocks = {"ln": leaf(L, (3,), 1), "w_gate": leaf(L, (3, 3), 2)}
    mixers = {m: sum(T.mixer_of(k) == m for k in kinds) for m in T.MIXERS}
    if not by_mixer:
        return {**blocks, "w": leaf(L, (3, 3), 3), "b": leaf(L, (3,), 4)}
    for at, (m, n) in enumerate(mixers.items()):
        if n:
            blocks[m] = {"w": leaf(n, (3, 3), 5 + at),
                         "b": leaf(n, (3,), 11 + at)}
    return blocks


def _layer(x, lp, kind, smooth: bool):
    # (the order of a stack's layers and the mixer a layer reads both show)
    y = (x * lp["ln"]) @ lp["w"] + lp["b"] * (2.0 if kind == "full" else 1.0)
    y = y @ lp["w_gate"]
    return jnp.tanh(y) + x if smooth else y % 64 + x


def _scanned(blocks, x, kinds, smooth):
    def body_of(period, first):
        def body(x, lps):
            seen = []
            for i, kind in enumerate(period):
                x = _layer(x, T.period_layer(lps, period, i), kind, smooth)
                seen.append(x)
            return x, jnp.stack(seen)
        return body

    x, outs = T.scan_periods(body_of, x, blocks, kinds)
    return x, jnp.concatenate([o.reshape((-1,) + o.shape[2:]) for o in outs])


def _looped(blocks, x, kinds, smooth):
    """Layer ``i``'s leaves by ``a[i]``, its mixer's by its count."""
    seen, nth = [], dict.fromkeys(T.MIXERS, 0)
    for i, kind in enumerate(kinds):
        m = T.mixer_of(kind)
        lp = {k: v[i] for k, v in blocks.items() if k not in T.MIXERS}
        if m in blocks:
            lp.update({k: v[nth[m]] for k, v in blocks[m].items()})
        nth[m] += 1
        x = _layer(x, lp, kind, smooth)
        seen.append(x)
    return x, jnp.stack(seen)


@pytest.mark.parametrize("by_mixer", [True, False],
                         ids=["by-mixer-and-layer", "by-layer"])
@pytest.mark.parametrize("letters", sorted(STACKS))
def test_a_cut_run_hands_each_step_its_own_layers(letters, by_mixer):
    """The carry and every layer's output of the scans equal, to the bit,
    a plain loop's over ``a[i]`` (whole numbers under 2**24: no sum
    rounds, so no order of fusion shows)."""
    kinds = tuple(_KINDS[k] for k in letters)
    assert [(a, "".join(k[0] for k in p), n)
            for a, p, n in T.kind_runs(kinds)] == STACKS[letters]
    blocks, x = _blocks(kinds, by_mixer), jnp.arange(3.0)
    got = jax.jit(lambda b, x: _scanned(b, x, kinds, False))(blocks, x)
    want = _looped(blocks, x, kinds, False)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("letters", sorted(STACKS))
def test_a_cut_runs_gradients_are_the_sliced_forms(letters):
    """Under ``jax.grad`` each step's cotangent is added into the whole
    leaf's at the step's own layers: the gradients of every leaf are the
    plain loop's, which slices. In float64, where the order of a sum is
    worth 1e-15 and a wrong layer ~1."""
    kinds = tuple(_KINDS[k] for k in letters)

    def loss(form):
        def of(blocks, x):
            last, seen = form(blocks, x, kinds, True)
            return jnp.sum(last ** 2) + jnp.sum(seen * jnp.arange(1.0, 4.0))
        return jax.jit(jax.grad(of, argnums=(0, 1)))

    with jax.enable_x64(True):
        blocks = jax.tree.map(
            lambda a: a.astype(jnp.float64),
            _blocks(kinds, True, jax.random.PRNGKey(7)))
        x = jnp.linspace(-1.0, 1.0, 3, dtype=jnp.float64)
        got, want = loss(_scanned)(blocks, x), loss(_looped)(blocks, x)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == jnp.float64 and float(jnp.abs(w).max()) > 0
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-9, atol=1e-12)
