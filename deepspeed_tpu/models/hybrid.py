"""Layers of more than one kind in one stack: the mixers of a stack whose
``TransformerConfig.layer_kinds`` alternate (SambaY, arXiv:2507.06607; the
``phi4flash`` family), as functions of ROWS.

Every layer of such a stack is ``h += Mixer(LN1(h)); h += MLP(LN2(h))``
and the mixer is one of

* ``mamba``: a selective state-space layer (Mamba-1, arXiv:2312.00752): a
  depthwise causal convolution and a diagonal linear recurrence, whose
  state per sequence is the convolution's last inputs and one
  ``[state, inner]`` matrix, whatever the sequence's length;
* ``window`` / ``full``: differential attention (arXiv:2410.05258) over
  the layer's own keys and values, under a window or fully causal;
* ``cross``: the same attention with queries alone, over the keys and
  values the stack's ``full`` layer wrote (YOCO, arXiv:2405.05254);
* ``gmu``: a gated memory unit, which gates the scan output (``memory``)
  the last ``mamba`` layer handed on.

Beside them :func:`short_conv`, the mixer of the ``conv`` layers that stand
among standard attention blocks (``TransformerConfig.standard_blocks``; the
``lfm2`` family): the segmented convolution below without a scan; and
:func:`kda_inputs` / :func:`delta_rule` / :func:`kda_output`, the mixer of
the ``kda`` layers (Kimi Delta Attention, the ``kimi_linear`` family): a
gated delta rule whose state a sequence is one ``[keys, values]`` matrix a
head in float32 and the last inputs of three short convolutions; and
:func:`mamba2_inputs` / :func:`ssd` / :func:`mamba2_output`, the mixer of
the ``mamba2`` layers (Mamba-2's state-space duality, the ``nemotron_h``
family): one decay a head, ``B`` and ``C`` shared by a group's heads, a
``[channels, state]`` matrix a head in float32 and the last inputs of one
convolution.

Rows are a flat batch ``[T, ...]`` in which a sequence's rows are
consecutive and in order (a SplitFuse tick; a dense ``[B, S]`` batch
flattened is the same thing with every run starting at position 0), so the
convolution and the recurrence are SEGMENTED: a run's first row starts
from the state handed in for it, and the state after every row comes back.
Nothing here knows of pools, slots or engines (``models/paged.py``) and
nothing imports ``models/transformer.py``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: kinds that attend, and of those the ones that project keys and values
ATTENTION_KINDS = ("window", "full", "cross")
KINDS = ("mamba", "gmu") + ATTENTION_KINDS
#: the products' head layout: see :func:`paired_queries`
PAIR = 2


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #

def mixer_specs(cfg: Any, kind: str) -> Dict[str, Tuple[tuple, tuple, str]]:
    """A ``kind`` layer's mixer leaves: name -> (shape, logical axes, how
    it starts). Matrices are stored ``[in, out]``; the state-space leaves
    keep ``inner`` minor (the TPU's lanes)."""
    h, di = cfg.hidden_size, cfg.ssm_inner
    if kind == "mamba":
        n, r, c = cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
        return {
            "w_in": ((h, 2 * di), ("embed", "mlp"), "std"),
            "conv_w": ((c, di), (None, "mlp"), "conv"),
            "conv_b": ((di,), ("mlp",), "zeros"),
            "w_x": ((di, r + 2 * n), ("mlp", None), "std"),
            "w_dt": ((r, di), (None, "mlp"), "std"),
            "b_dt": ((di,), ("mlp",), "dt_bias"),
            "a_log": ((n, di), (None, "mlp"), "a_log"),
            "skip_scale": ((di,), ("mlp",), "ones"),
            "wo": ((di, h), ("mlp", "embed"), "out"),
        }
    if kind == "conv":
        return {"w_in": ((h, 3 * h), ("embed", "mlp"), "std"),
                "conv_w": ((cfg.conv_taps, h), (None, "mlp"), "conv"),
                "wo": ((h, h), ("mlp", "embed"), "out")}
    if kind == "gmu":
        return {"w_in": ((h, di), ("embed", "mlp"), "std"),
                "wo": ((di, h), ("mlp", "embed"), "out")}
    if kind == "kda":
        n, d, r, c = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank,
                      cfg.kda_conv)
        w = n * d
        specs = {"w_fa": ((h, r), ("embed", None), "std"),
                 "w_fb": ((r, w), (None, "heads"), "std"),
                 "dt_bias": ((w,), ("heads",), "dt_bias"),
                 "a_log": ((n,), (None,), "a_log_heads"),
                 "w_b": ((h, n), ("embed", None), "std"),
                 "w_ga": ((h, r), ("embed", None), "std"),
                 "w_gb": ((r, w), (None, "heads"), "std"),
                 "o_norm": ((d,), (None,), "ones"),
                 "wo": ((w, h), ("heads", "embed"), "out")}
        for x in "qkv":
            specs[f"w{x}"] = ((h, w), ("embed", "heads"), "std")
            specs[f"conv_{x}"] = ((c, w), (None, "heads"), "conv")
        return specs
    if kind == "mamba2":
        nh, p, c = cfg.mamba2_heads, cfg.mamba2_head_dim, cfg.mamba2_conv
        di, bc = nh * p, 2 * cfg.mamba2_groups * cfg.mamba2_state
        return {"w_in": ((h, 2 * di + bc + nh), ("embed", "mlp"), "std"),
                "conv_w": ((c, di + bc), (None, "mlp"), "conv"),
                "conv_b": ((di + bc,), ("mlp",), "zeros"),
                "dt_bias": ((nh,), (None,), "dt_bias"),
                "a_log": ((nh,), (None,), "a_log_heads"),
                "skip_scale": ((nh,), (None,), "ones"),
                "gate_norm": ((di,), ("mlp",), "ones"),
                "wo": ((di, h), ("mlp", "embed"), "out")}
    if kind not in ATTENTION_KINDS:
        raise ValueError(f"unknown layer kind {kind!r}; one of {KINDS}")
    d = cfg.head_dim
    qdim, kvdim = cfg.num_heads * d, cfg.kv_heads * d
    specs = {"wq": ((h, qdim), ("embed", "heads"), "std"),
             "wo": ((qdim, h), ("heads", "embed"), "out"),
             "sub_norm": ((PAIR * d,), (None,), "ones")}
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        specs[name] = ((d,), (None,), "lambda")
    if kind != "cross":
        specs["wk"] = ((h, kvdim), ("embed", "kv_heads"), "std")
        specs["wv"] = ((h, kvdim), ("embed", "kv_heads"), "std")
    return specs


def init_leaf(how: str, shape: tuple, key: jax.Array, std: float,
              out_std: float) -> jax.Array:
    """float32 start of one stacked leaf ``[layers, ...]``."""
    if how in ("std", "out"):
        return jax.random.normal(key, shape, jnp.float32) * (
            std if how == "std" else out_std)
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    if how == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if how == "lambda":                  # DIFF Transformer: N(0, 0.1)
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    if how == "conv":                    # U(+-1/sqrt(taps)), as a conv1d's
        bound = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if how == "a_log":                   # A = -(1 .. state), every channel
        n = shape[-2]
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], shape)
    if how == "a_log_heads":             # A = -U(1, 16), one a head
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how == "dt_bias":                 # softplus^-1 of dt ~ logU[1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(how)


# --------------------------------------------------------------------------- #
# runs of rows
# --------------------------------------------------------------------------- #

class Runs(NamedTuple):
    """Which rows of a flat batch continue the row before them."""
    start: jax.Array     # [T] bool: the row opens a run
    last: jax.Array      # [T] bool: the row closes one
    offset: jax.Array    # [T] int32: rows of its run before it
    fresh: jax.Array     # [T] bool: its run starts at position 0


def runs_of(owner: jax.Array, positions: jax.Array) -> Runs:
    """``owner`` [T]: what a row's sequence is told by (its slot; a dense
    batch's row index); a row continues the one before it where the owner
    is the same and the position is the next."""
    t = jnp.arange(owner.shape[0], dtype=jnp.int32)
    start = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (owner[1:] != owner[:-1]) | (positions[1:] != positions[:-1] + 1)])
    first = lax.cummax(jnp.where(start, t, 0))
    offset = t - first
    last = jnp.concatenate([start[1:], jnp.ones((1,), jnp.bool_)])
    return Runs(start, last, offset, positions - offset == 0)


# --------------------------------------------------------------------------- #
# the state-space layer
# --------------------------------------------------------------------------- #

def _segmented_conv(x: jax.Array, taps: jax.Array, runs: Runs,
                    conv0: Tuple[jax.Array, ...]
                    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Depthwise causal convolution over runs. x [T, di]; taps [c, di]
    (the last tap meets the row itself); conv0: the c-1 inputs before each
    row's RUN, oldest first, each [T, di] (read at the run's rows only;
    zeroed here for a run at position 0, whatever was handed in). A tuple
    of rows and not one ``[T, c-1, di]`` array, as a state store keeps
    them (``paged.init_paged_kv``): a dimension of 2 or 3 taps ahead of the
    lanes pads every tile it meets. Returns (the convolution [T, di] in
    float32, the c-1 inputs up to and including each row, oldest first)."""
    c = taps.shape[0]
    fresh = runs.fresh[:, None]
    conv0 = [jnp.where(fresh, 0, s).astype(x.dtype) for s in conv0]
    before = [x]                          # before[k][t]: the input k rows back
    for k in range(1, c):
        # k rows back lies in the run, or (k - offset) rows before it:
        # the stored input ``c - 1 - k + offset``
        back = conv0[c - 1 - k]
        for o in range(1, k):
            back = jnp.where((runs.offset == o)[:, None],
                             conv0[c - 1 - k + o], back)
        before.append(jnp.where((runs.offset >= k)[:, None],
                                jnp.pad(x, ((k, 0), (0, 0)))[:-k], back))
    taps = taps.astype(jnp.float32)
    out = sum(before[k].astype(jnp.float32) * taps[c - 1 - k]
              for k in range(c))
    return out, tuple(before[c - 2::-1])


def _selective_scan(delta: jax.Array, xc: jax.Array, bm: jax.Array,
                    cm: jax.Array, a_neg: jax.Array, runs: Runs,
                    ssm0: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The recurrence ``s[t] = exp(delta[t] A) * s[t-1] + (delta[t] x[t])
    B[t]^T`` and its read-out ``y[t] = C[t] s[t]`` along rows, where a
    run's first row takes ``ssm0[t]`` for ``s[t-1]``. delta, xc [T, di];
    bm, cm [T, n]; a_neg [n, di]; ssm0 [T, n, di]; float32. Returns (the
    state after every row [T, n, di], y [T, di]).

    One row after the other with the state carried, in plain ``lax``:
    which rows start a run is data, so one program serves every tick. On
    the v5e a 512-row tick's nine scans take 10 ms this way; an
    associative scan over ``(decay, drive)`` pairs took 66 ms (some twenty
    passes over [512, n, di]) and a blocked form of it 36 (PERF.md,
    PR 31)."""
    def step(s, row):
        d, x, b, c, start, s0 = row
        s = jnp.exp(d[None, :] * a_neg) * jnp.where(start, s0, s) \
            + (d * x)[None, :] * b[:, None]
        return s, (s, c @ s)

    _, (s, y) = lax.scan(step, jnp.zeros_like(ssm0[0]),
                         (delta, xc, bm, cm, runs.start, ssm0), unroll=8)
    return s, y


def mamba(h: jax.Array, lp: Dict[str, Any], cfg: Any, runs: Runs,
          conv0: Tuple[jax.Array, ...], ssm0: jax.Array
          ) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...], jax.Array]:
    """The selective state-space mixer on normed rows h [T, H], before its
    output projection. conv0 (c-1 inputs [T, di], oldest first), ssm0
    [T, n, di]: the state each row's run starts from (zeroed here for a run
    at position 0, whatever was handed in). Returns (gated output [T, di],
    ungated scan output ``y`` [T, di] (the memory a gated unit reads), and
    the state after every row: conv (c-1 inputs [T, di]), ssm [T, n, di]
    float32)."""
    dt_, di, n = h.dtype, cfg.ssm_inner, cfg.ssm_state
    r = cfg.ssm_dt_rank
    ssm0 = jnp.where(runs.fresh[:, None, None], 0.0, ssm0)
    with jax.named_scope("ssm_proj"):
        xz = h @ lp["w_in"].astype(dt_)
        x, z = xz[:, :di], xz[:, di:]
    with jax.named_scope("ssm_conv"):
        conv, conv_new = _segmented_conv(x, lp["conv_w"], runs, conv0)
        xc = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))
    with jax.named_scope("ssm_proj"):
        dbc = (xc.astype(dt_) @ lp["w_x"].astype(dt_))
        delta = jax.nn.softplus(
            (dbc[:, :r] @ lp["w_dt"].astype(dt_)).astype(jnp.float32)
            + lp["b_dt"].astype(jnp.float32))                    # [T, di]
        bm = dbc[:, r:r + n].astype(jnp.float32)                 # [T, n]
        cm = dbc[:, r + n:].astype(jnp.float32)
    with jax.named_scope("ssm_scan"):
        a_neg = -jnp.exp(lp["a_log"].astype(jnp.float32))        # [n, di]
        s, y = _selective_scan(delta, xc, bm, cm, a_neg, runs, ssm0)
        y = y + lp["skip_scale"].astype(jnp.float32) * xc
    with jax.named_scope("ssm_gate"):
        y = y.astype(dt_)
        out = y * jax.nn.silu(z)
    return out, y, conv_new, s


def short_conv(h: jax.Array, lp: Dict[str, Any], runs: Runs,
               conv0: Tuple[jax.Array, ...]
               ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """The gated short convolution (the ``lfm2`` family's ``conv`` layers)
    on normed rows h [T, H], before its output projection: ``[B | C | z]
    = h W_in``, ``g = B * z``, a depthwise causal convolution of ``g``
    over the row's run (no bias, no activation, no scan), times ``C``.
    conv0: the taps-1 inputs ``g`` before each row's run, oldest first,
    each [T, H] (zeroed for a run at position 0, whatever was handed in).
    Returns (the gated output [T, H], ``g`` up to and including each row,
    taps-1 of [T, H]: a sequence's state is its last row's)."""
    dt_, H = h.dtype, lp["conv_w"].shape[-1]
    bcz = h @ lp["w_in"].astype(dt_)
    g = bcz[:, :H] * bcz[:, 2 * H:]
    conv, conv_new = _segmented_conv(g, lp["conv_w"], runs, conv0)
    return bcz[:, H:2 * H] * conv.astype(dt_), conv_new


# --------------------------------------------------------------------------- #
# Kimi Delta Attention: a gated delta rule
# --------------------------------------------------------------------------- #

#: rows of a tick the one-row form of the rule takes (:func:`delta_rule`)
KDA_STEP_ROWS = 256
L2_EPS = 1e-6


def kda_state_shapes(cfg: Any) -> Tuple[tuple, tuple]:
    """What a sequence keeps in a ``kda`` layer: (the rule's matrix
    ``[heads, keys, values]``, float32; the last inputs of the three
    convolutions ``[taps - 1, 3 x heads x keys]``, q | k | v)."""
    n, d = cfg.kda_heads, cfg.kda_head_dim
    return (n, d, d), (cfg.kda_conv - 1, 3 * n * d)


def kda_inputs(h: jax.Array, lp: Dict[str, Any], cfg: Any, runs: Runs,
               conv0: Tuple[jax.Array, ...]):
    """A ``kda`` layer's normed rows h [T, H] up to the rule. ``q~, k~,
    v~ = h W_q, h W_k, h W_v`` each through its own depthwise causal
    convolution over the row's run (conv0: the taps-1 inputs before each
    row's run, oldest first, each [T, 3 N D], q | k | v; zeroed for a run at
    position 0)
    and SiLU; per head ``q = l2norm(q) D^-0.5``, ``k = l2norm(k)``; the
    log-decay a head and CHANNEL ``g = -exp(A_log) softplus(W_fb (W_fa h) +
    dt_bias)`` (``a = exp(g)`` in (0, 1)); the step size ``b = sigmoid(h
    W_b)``, one a head. Returns ((q, k, v, g [T, N, D], b [T, N]), all
    float32, and the convolutions' inputs up to and including each row,
    taps-1 of [T, 3 N D])."""
    dt_, f32 = h.dtype, jnp.float32
    Tn, n, d = h.shape[0], cfg.kda_heads, cfg.kda_head_dim
    qkv = jnp.concatenate([h @ lp[f"w{x}"].astype(dt_) for x in "qkv"],
                          axis=-1)
    taps = jnp.concatenate([lp[f"conv_{x}"] for x in "qkv"], axis=-1)
    conv, conv_new = _segmented_conv(qkv, taps, runs, conv0)
    q, k, v = (x.reshape(Tn, n, d) for x in jnp.split(
        jax.nn.silu(conv), 3, axis=-1))

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    f = (h @ lp["w_fa"].astype(dt_)) @ lp["w_fb"].astype(dt_)
    g = -jnp.exp(lp["a_log"].astype(f32))[None, :, None] * jax.nn.softplus(
        f.astype(f32) + lp["dt_bias"].astype(f32)).reshape(Tn, n, d)
    b = jax.nn.sigmoid((h @ lp["w_b"].astype(dt_)).astype(f32))
    return (l2norm(q) * d ** -0.5, l2norm(k), v, g, b), conv_new


def kda_output(o: jax.Array, h: jax.Array, lp: Dict[str, Any], cfg: Any
               ) -> jax.Array:
    """From the rule's read-out o [T, N, D] (float32) to the mixer's output
    before ``wo`` [T, N D]: RMSNorm over each head's D with one gain, times
    ``sigmoid(W_gb (W_ga h))``."""
    dt_, f32 = h.dtype, jnp.float32
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + cfg.norm_eps) * lp["o_norm"].astype(f32)
    gate = (h @ lp["w_ga"].astype(dt_)) @ lp["w_gb"].astype(dt_)
    return (o.reshape(o.shape[0], -1)
            * jax.nn.sigmoid(gate.astype(f32))).astype(dt_)


def kda_recurrence(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   b: jax.Array, runs: Runs, s0: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """The rule one row after another, the arbiter of the two forms below:
    ``S' = diag(exp g) S``; ``S = S' + b k (v - S'^T k)^T``; ``o = S^T q``
    a head, a run's first row taking ``s0[t]`` [N, D, D] for the state
    before it. Returns (o [T, N, D], the state after every row
    [T, N, D, D]): for tests and small sizes only."""
    def step(s, row):
        q_, k_, v_, g_, b_, start, s0_ = row
        s = jnp.exp(g_)[..., None] * jnp.where(start, s0_, s)
        u = b_[:, None] * (v_ - jnp.einsum("nk,nkv->nv", k_, s))
        s = s + k_[..., None] * u[:, None, :]
        return s, (jnp.einsum("nk,nkv->nv", q_, s), s)

    _, (o, s) = lax.scan(step, jnp.zeros_like(s0[0]),
                         (q, k, v, g, b, runs.start, s0))
    return o, s


def _runs_of_one(runs: Runs, slot: jax.Array, most: int):
    """The rows of a tick a one-row form takes: the runs of ONE row of real
    sequences (``slot`` > 0), the first ``most`` of them. Returns (which
    rows are real [T], which the form takes [T], those rows' indices
    gathered [min(T, most)] with the index past the end for the places no
    row takes (a scatter drops them), the same clipped for a gather, and
    which places a row took)."""
    Tn = slot.shape[0]
    real = slot > 0
    alone = runs.start & runs.last & real
    nth = jnp.cumsum(alone) - 1
    step_row = alone & (nth < most)
    rows = jnp.nonzero(step_row, size=min(Tn, most), fill_value=Tn)[0]
    return real, step_row, rows, jnp.clip(rows, 0, Tn - 1), rows < Tn


def delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               b: jax.Array, runs: Runs, state: jax.Array, slot: jax.Array,
               use_kernel: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The rule over a flat batch of rows in runs. ``state``
    [rows of state, N, D, D] float32 holds a matrix a sequence; row t's
    sequence is ``slot[t]`` (0: a pad row, which reads and writes nothing).
    A run reads its sequence's matrix at its first row (zero where the run
    starts at position 0, whatever is stored) and writes it after its last;
    between the two it exists in neither form below.

    Runs of ONE row (decode rows), the first ``KDA_STEP_ROWS`` of them,
    take the one-row form (``ops.pallas.kda.kda_step``: one read and one
    write of the matrix); every other run the chunkwise form
    (``ops.pallas.kda.kda_chunk``); both as Mosaic kernels where
    ``use_kernel``, else their references in plain XLA. Returns (o [T, N,
    D] float32, state)."""
    from deepspeed_tpu.ops.pallas import kda as K

    real, step_row, rows, at, took = _runs_of_one(runs, slot, KDA_STEP_ROWS)
    step = K.kda_step if use_kernel else K.kda_step_reference
    o_step, state = step(
        q[at], k[at], v[at], jnp.exp(g[at]), b[at], state,
        jnp.where(took, slot[at], 0), (runs.fresh[at] & took))
    chunk = K.kda_chunk if use_kernel else K.kda_chunk_reference
    o, state = chunk(q, k, v, g, b, runs, real & ~step_row, state, slot)
    return o.at[rows].set(o_step, mode="drop"), state


# --------------------------------------------------------------------------- #
# Mamba-2: state-space duality
# --------------------------------------------------------------------------- #

#: rows of a tick the one-row form of the recurrence takes (:func:`ssd`)
SSD_STEP_ROWS = 256


def mamba2_state_shapes(cfg: Any) -> Tuple[tuple, tuple]:
    """What a sequence keeps in a ``mamba2`` layer: (the recurrence's
    matrices, ``[channels, state]`` a head in float32, as the store lays
    them out (``ops.pallas.ssd.store_shape``: the state values down a
    tile's rows, two heads of 64 channels along its lanes); the last inputs
    of the convolution ``[taps - 1, heads x channels + 2 x groups x
    state]``, x | B | C)."""
    from deepspeed_tpu.ops.pallas.ssd import store_shape

    nh, p = cfg.mamba2_heads, cfg.mamba2_head_dim
    return store_shape(nh, cfg.mamba2_groups, p, cfg.mamba2_state), (
        cfg.mamba2_conv - 1,
        nh * p + 2 * cfg.mamba2_groups * cfg.mamba2_state)


def mamba2_inputs(h: jax.Array, lp: Dict[str, Any], cfg: Any, runs: Runs,
                  conv0: Tuple[jax.Array, ...]):
    """A ``mamba2`` layer's normed rows h [T, H] up to the recurrence.
    ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv(xBC) + b)``, depthwise
    and causal over the row's run (conv0: the taps-1 inputs before each
    row's run, oldest first, each [T, inner + 2 G N]; zeroed for a run at
    position 0); ``[x | B | C] = xBC``; ``delta = softplus(dt + dt_bias)``
    a head (no clamp: the family's ``time_step_min`` / ``_max`` / ``_floor``
    shape the bias's start only); the log-decay a head ``g = -exp(A_log)
    delta`` (``a = exp(g)`` in (0, 1)). Returns ((x [T, nh, P], delta
    [T, nh], g [T, nh], B, C [T, G, N]) float32, z [T, inner] in h's type,
    the convolution's inputs up to and including each row)."""
    dt_, f32 = h.dtype, jnp.float32
    Tn, nh, p = h.shape[0], cfg.mamba2_heads, cfg.mamba2_head_dim
    G, N = cfg.mamba2_groups, cfg.mamba2_state
    di = nh * p
    # computed ONCE: its three readers (the gate last of all) are far apart,
    # and XLA otherwise clones the whole product for each of them (13
    # `.remat` copies of bf16[2048, 18560] in a five-layer chunk tick, 1.7
    # ms each on the v5e: PERF.md, PR 53)
    zxd = lax.optimization_barrier(h @ lp["w_in"].astype(dt_))
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * G * N], \
        zxd[:, 2 * di + 2 * G * N:]
    conv, conv_new = _segmented_conv(xbc, lp["conv_w"], runs, conv0)
    xbc = jax.nn.silu(conv + lp["conv_b"].astype(f32))
    x = xbc[:, :di].reshape(Tn, nh, p)
    B = xbc[:, di:di + G * N].reshape(Tn, G, N)
    C = xbc[:, di + G * N:].reshape(Tn, G, N)
    delta = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    g = -jnp.exp(lp["a_log"].astype(f32)) * delta
    return (x, delta, g, B, C), z, conv_new


def mamba2_output(y: jax.Array, x: jax.Array, z: jax.Array,
                  lp: Dict[str, Any], cfg: Any) -> jax.Array:
    """From the recurrence's read-out y [T, nh, P] (float32) to the mixer's
    output before ``wo`` [T, inner]: the skip ``+ D_h x``, the gate BEFORE
    the norm, ``y silu(z)``, then RMSNorm over each GROUP's ``inner / G``
    channels with one gain a channel."""
    f32 = jnp.float32
    Tn, G = y.shape[0], cfg.mamba2_groups
    # flat, a row's heads x channels along the lanes, as the mixer made x
    # and the recurrence's kernels hand y: an array [T, 128, 64] of the
    # tick either pads its lanes or is re-laid heads-minor and back (five
    # copies of 67 MB a layer of a 2,048-row tick: PERF.md, PR 61)
    skip = jnp.repeat(lp["skip_scale"].astype(f32), x.shape[-1])
    y = y.reshape(Tn, -1) + skip[None, :] * x.reshape(Tn, -1)
    y = y * jax.nn.silu(z.astype(f32))
    yg = y.reshape(Tn, G, -1)
    yg = yg * lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                        + cfg.norm_eps)
    return (yg.reshape(Tn, -1) * lp["gate_norm"].astype(f32)).astype(z.dtype)


def ssd_recurrence(x: jax.Array, delta: jax.Array, g: jax.Array,
                   B: jax.Array, C: jax.Array, runs: Runs, s0: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence one row after another, the arbiter of the two forms
    below: a head ``S = exp(g) S + delta x B^T``; ``y = S C`` (``B``, ``C``
    its group's), a run's first row taking ``s0[t]`` [nh, P, N] for the
    state before it. Returns (y [T, nh, P], the state after every row
    [T, nh, P, N]): for tests and small sizes only."""
    rep = x.shape[1] // B.shape[1]

    def step(s, row):
        x_, d_, g_, b_, c_, start, s0_ = row
        b_, c_ = jnp.repeat(b_, rep, axis=0), jnp.repeat(c_, rep, axis=0)
        s = jnp.exp(g_)[:, None, None] * jnp.where(start, s0_, s) \
            + (d_[:, None] * x_)[..., None] * b_[:, None, :]
        return s, (jnp.einsum("hpn,hn->hp", s, c_), s)

    _, (y, s) = lax.scan(step, jnp.zeros_like(s0[0]),
                         (x, delta, g, B, C, runs.start, s0))
    return y, s


def ssd(x: jax.Array, delta: jax.Array, g: jax.Array, B: jax.Array,
        C: jax.Array, runs: Runs, state: jax.Array, slot: jax.Array,
        chunk: int = 128, use_kernel: bool = False
        ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a flat batch of rows in runs. ``state`` [rows of
    state, *``ops.pallas.ssd.store_shape``] float32 holds a matrix a head a
    sequence; row t's
    sequence is ``slot[t]`` (0: a pad row, which reads and writes nothing).
    A run reads its sequence's state at its first row (zero where the run
    starts at position 0, whatever is stored) and writes it after its last.

    Runs of ONE row (decode rows), the first ``SSD_STEP_ROWS`` of them,
    take the one-row form (``ops.pallas.ssd.ssd_step``: one read and one
    write of the state, in place; a Mosaic kernel where ``use_kernel``);
    every other run the chunked form (``ops.pallas.ssd.ssd_chunk``: chunks
    of ``chunk`` rows, matrix products within a chunk, the state carried
    across; a Mosaic kernel where ``use_kernel``, else its plain
    reference). Returns (y [T, nh, P] float32, state)."""
    from deepspeed_tpu.ops.pallas import ssd as K

    real, step_row, rows, at, took = _runs_of_one(runs, slot, SSD_STEP_ROWS)
    step = K.ssd_step if use_kernel else K.ssd_step_reference
    chunked = K.ssd_chunk if use_kernel else K.ssd_chunk_reference
    # rows are taken from x and put into y flat (``mamba2_output`` says why)
    flat = x.reshape(x.shape[0], -1)
    with jax.named_scope("ssd_step"):
        y_step, state = step(
            flat[at].reshape((-1,) + x.shape[1:]), delta[at], jnp.exp(g[at]),
            B[at], C[at], state, jnp.where(took, slot[at], 0),
            runs.fresh[at] & took)
    y, state = chunked(x, delta, g, B, C, runs, real & ~step_row, state,
                       slot, chunk)
    y = y.reshape(flat.shape).at[rows].set(
        y_step.reshape(-1, flat.shape[1]), mode="drop")
    return y.reshape(x.shape), state


def gmu(h: jax.Array, lp: Dict[str, Any], memory: jax.Array) -> jax.Array:
    """Gated memory unit on normed rows, before its output projection."""
    return jax.nn.silu(h @ lp["w_in"].astype(h.dtype)) * memory


# --------------------------------------------------------------------------- #
# differential attention
# --------------------------------------------------------------------------- #

def lambda_init(layer: jax.Array) -> jax.Array:
    """``0.8 - 0.6 exp(-0.3 l)`` of the layer's index from 0."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def paired_queries(q: jax.Array, odd: Optional[jax.Array] = None
                   ) -> jax.Array:
    """Differential attention as ONE grouped-query attention. Heads pair by
    parity (``q1 = q[0::2]``, ``k1 = k[0::2]`` ...), so key heads ``2g,
    2g+1`` side by side are one key of ``2 D`` columns ``[k1_g | k2_g]``,
    the values likewise ``[v1_g | v2_g]``: exactly how ``[.., K, D]`` keys
    and values lie in memory, read as ``[.., K/2, 2 D]``. A query of the
    first softmax is ``[q1 | 0]`` and one of the second ``[0 | q2]``: the
    score is ``q1 . k1`` or ``q2 . k2`` and either softmax's output is
    over ``[v1 | v2]``, which are the four products. Query heads
    ``4g .. 4g+3`` (``q1, q2, q1, q2``) belong to paired key head ``g``,
    so no head moves. q [T, N, D] -> [T, N, 2 D]. ``odd`` [N] bool: the
    query heads that take the pair's second half, where that is not the
    heads of odd index (``paged._lane_packed``: grouped queries on KV
    heads stored two to a row)."""
    zeros = jnp.zeros_like(q)
    first = jnp.concatenate([q, zeros], axis=-1)
    second = jnp.concatenate([zeros, q], axis=-1)
    if odd is None:
        odd = jnp.arange(q.shape[1]) % PAIR == 1
    return jnp.where(odd[None, :, None], second, first)


def paired_cache(x: jax.Array) -> jax.Array:
    """Keys or values [..., K, D] -> [..., K/2, 2 D] (a reshape)."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // PAIR, PAIR * x.shape[-1]))


def differential_merge(o: jax.Array, lp: Dict[str, Any], layer: jax.Array,
                       eps: float) -> jax.Array:
    """From the paired attention's output o [T, N, 2 D] (head ``2j`` the
    first softmax of pair ``j``, ``2j+1`` the second) to the layer's
    attention before ``wo`` [T, N D]: ``o1 - lam o2``, RMSNorm over the
    pair's ``2 D`` columns, times ``1 - lam0``."""
    Tn, N, D2 = o.shape
    lam0 = lambda_init(layer)
    f32 = jnp.float32
    lam = jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32)
                          * lp["lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32)
                          * lp["lambda_k2"].astype(f32))) + lam0
    o = o.astype(f32).reshape(Tn, N // PAIR, PAIR, D2)
    d = o[:, :, 0] - lam * o[:, :, 1]
    d = d * lax.rsqrt(jnp.mean(jnp.square(d), axis=-1, keepdims=True) + eps)
    d = d * lp["sub_norm"].astype(f32) * (1.0 - lam0)
    return d.reshape(Tn, N // PAIR * D2)


def windowed_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       scale: float, window: int,
                       chosen: Optional[jax.Array] = None) -> jax.Array:
    """Plain causal attention of a dense batch under a window (0: none).
    q [B, S, N, D]; k, v [B, S, K, D]; position j is visible from i iff
    ``i - window < j <= i`` and, with ``chosen [B, S, S]`` bool, it is
    chosen for i. float32 softmax."""
    S, N, K = q.shape[1], q.shape[2], k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    s = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window:
        seen &= j > i - window
    seen = seen[None, None] if chosen is None \
        else seen[None, None] & chosen[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bnst,btnd->bsnd", p.astype(q.dtype), v)
