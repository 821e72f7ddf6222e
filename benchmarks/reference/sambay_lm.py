"""Plain reference: the ``phi4flash`` family's decoder (SambaY,
arXiv:2507.06607): Mamba-1 layers (arXiv:2312.00752), differential attention
(arXiv:2410.05258) under a window, ONE full-attention layer whose keys and
values the later cross-attention layers read (YOCO, arXiv:2405.05254), and
gated memory units between them. Forward pass in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.

Written from the equations (ISSUE 31, from the papers above), not from the
program: it imports nothing of ``deepspeed_tpu``. It reads the *layout* of
the program's parameter tree because the weights under test are the
program's own: layers pair up, each run of equal pairs is one subtree
``<kind>_<kind>_blocks`` holding ``{kind: leaves stacked by pair}``;
matrices are stored ``[in, out]``; a Mamba layer's ``a_log`` and its state
are ``[state, inner]``. No cache, no kernel, no ring, no paired-head trick:
the state-space layers are a sequential ``lax.scan`` over positions, the
window is an explicit ``[S, S]`` mask, and the four products of
differential attention are written out.

Every layer ``l`` is ``h += Mixer_l(LN1(h)); h += MLP(LN2(h))``,
``MLP(u) = W2 (silu(W_gate u) * (W_up u))``; LayerNorm with bias. By index
(``kinds``): even ``l <= L/2``: Mamba; odd ``l < L/2 + 1``: windowed
differential attention; ``l = L/2 + 1``: the same, fully causal, and the
owner of the shared keys and values; beyond it, even: gated memory unit on
the last Mamba layer's ungated scan output at the same position; odd: cross
attention (queries alone) over the shared keys and values.

Departures from the papers, each deliberate:
* no attention bias and no dropout (the equations as ISSUE 31 gives them;
  the published file's ``embd_pdrop`` / ``resid_pdrop`` are 0);
* the gated unit's memory is the scan output INCLUDING the skip term
  ``D * x`` and before the ``silu(z)`` gate (``y_t`` of ISSUE 31);
* the output head is applied a slice of the vocabulary at a time into one
  buffer (200k columns in float32 beside a deployment do not fit twice);
* weights are whatever the caller passes, upcast to float32 a layer at a
  time.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_BLOCK = 16384


def kinds(n_layers: int) -> List[str]:
    full = n_layers // 2 + 1
    return [("mamba" if l % 2 == 0 else "window" if l < full else "full")
            if l <= full else ("gmu" if l % 2 == 0 else "cross")
            for l in range(n_layers)]


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "phi4flash":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    if hf.get("mb_per_layer", 2) != 2:
        raise ValueError("reference: mb_per_layer must be 2")
    h = hf["hidden_size"]
    return dict(
        layers=hf["num_hidden_layers"], heads=hf["num_attention_heads"],
        kv_heads=hf["num_key_value_heads"],
        head_dim=h // hf["num_attention_heads"],
        eps=hf["layer_norm_eps"], window=int(hf["sliding_window"]),
        # the family's defaults (the configuration file's ``assumed``)
        inner=int(hf.get("mamba_expand", 2)) * h,
        state=int(hf.get("mamba_d_state", 16)),
        dt_rank=int(hf.get("mamba_dt_rank", -(-h // 16))))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mamba(u, lp, arch) -> Tuple[jax.Array, jax.Array]:
    """u [S, H] -> (the mixer's output [S, H], its scan output y [S, di]
    before the gate). One position at a time."""
    di, n, r = arch["inner"], arch["state"], arch["dt_rank"]
    xz = u @ lp["w_in"]
    x, z = xz[:, :di], xz[:, di:]
    taps = lp["conv_w"]                               # [c, di], last = now
    c = taps.shape[0]
    xp = jnp.pad(x, ((c - 1, 0), (0, 0)))
    x = sum(xp[k:k + x.shape[0]] * taps[k] for k in range(c))
    x = jax.nn.silu(x + lp["conv_b"])
    dbc = x @ lp["w_x"]
    dt = jax.nn.softplus(dbc[:, :r] @ lp["w_dt"] + lp["b_dt"])      # [S, di]
    bm, cm = dbc[:, r:r + n], dbc[:, r + n:]                         # [S, n]
    a = -jnp.exp(lp["a_log"])                                        # [n, di]

    def step(s, t):
        dt_t, x_t, b_t, c_t = t
        s = jnp.exp(dt_t[None, :] * a) * s + (dt_t * x_t)[None, :] \
            * b_t[:, None]
        return s, c_t @ s

    _, y = jax.lax.scan(step, jnp.zeros((n, di), jnp.float32),
                        (dt, x, bm, cm))
    y = y + lp["skip_scale"] * x
    return (y * jax.nn.silu(z)) @ lp["wo"], y


def _softmax_rows(q, k, mask, d):
    """q [S, n, d], k [S, m, d] with n a multiple of m (query head j reads
    key head j // (n / m)) -> probabilities [n, S, S]."""
    k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    s = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(d)
    return jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)


def _differential(q, k, v, lp, layer, mask, arch):
    """q [S, N, d]; k, v [S, K, d] -> [S, N d] before the output
    projection. Heads pair by parity."""
    d = arch["head_dim"]
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    v1, v2 = v[:, 0::2], v[:, 1::2]
    rep = q1.shape[1] // v1.shape[1]
    v1, v2 = jnp.repeat(v1, rep, axis=1), jnp.repeat(v2, rep, axis=1)
    p1 = _softmax_rows(q1, k1, mask, d)
    p2 = _softmax_rows(q2, k2, mask, d)
    a1 = jnp.concatenate([jnp.einsum("nst,tnd->snd", p1, v1),
                          jnp.einsum("nst,tnd->snd", p1, v2)], axis=-1)
    a2 = jnp.concatenate([jnp.einsum("nst,tnd->snd", p2, v1),
                          jnp.einsum("nst,tnd->snd", p2, v2)], axis=-1)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)     # layer: float32 scalar
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam0
    o = a1 - lam * a2                                        # [S, N/2, 2d]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + arch["eps"]) * lp["sub_norm"]
    o = o * (1.0 - lam0)
    return o.reshape(o.shape[0], -1)


def _layer(x, lp, memory, shared, layer, step, kind: str, arch):
    """x [S, H]; ``memory`` [S, di] and ``shared`` (k, v) are handed from
    layer to layer and replaced by the layer that makes them. ``lp``: the
    leaves of the layer's kind as the program stacks them, of which this
    layer is index ``step`` (cut out here, inside the compiled function:
    cut outside it, every index of every leaf is a program of its own)."""
    lp = _f32(jax.tree.map(lambda a: a[step], lp))
    S = x.shape[0]
    N, K, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    u = _layer_norm(x, lp["ln1"], arch["eps"])
    if kind == "mamba":
        out, memory = _mamba(u, lp, arch)
    elif kind == "gmu":
        out = (jax.nn.silu(u @ lp["w_in"]) * memory) @ lp["wo"]
    else:
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        mask = j <= i
        if kind == "window":
            mask &= j > i - arch["window"]
        q = (u @ lp["wq"]).reshape(S, N, d)
        kv = shared
        if kind != "cross":
            kv = ((u @ lp["wk"]).reshape(S, K, d),
                  (u @ lp["wv"]).reshape(S, K, d))
        if kind == "full":
            shared = kv
        out = _differential(q, *kv, lp, layer, mask, arch) @ lp["wo"]
    x = x + out
    u = _layer_norm(x, lp["ln2"], arch["eps"])
    x = x + (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]
    return x, memory, shared


_layer_jit = jax.jit(_layer, static_argnames=("kind", "arch"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, x @ w.astype(jnp.float32), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _layer_params(params, kind_of: List[str]):
    """Layer by layer, (kind, the stacked leaves of that kind in the
    layer's run, the layer's index among them) from the program's tree:
    pairs of layers, runs of equal pairs stacked under one key."""
    pairs = [tuple(kind_of[i:i + 2]) for i in range(0, len(kind_of), 2)]
    run_start = 0
    for p, pair in enumerate(pairs):
        if p and pairs[p - 1] != pair:
            run_start = p
        sub = params["_".join(pair) + "_blocks"]
        for kind in pair:
            yield kind, sub[kind], p - run_start


def forward_logits(params, tokens, arch: Dict[str, Any], at=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with ``at`` (a list
    of positions) the logits of those positions alone, [B, len(at), V]."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        B, S = tokens.shape
        emb = jnp.asarray(params["tok_emb"])
        rows = []
        for b in range(B):
            x = emb[tokens[b]].astype(jnp.float32)
            memory = jnp.zeros((S, arch["inner"]), jnp.float32)
            shared = None
            for layer, (kind, lp, step) in enumerate(
                    _layer_params(params, kinds(arch["layers"]))):
                if shared is None:      # a pytree of one shape for the jit
                    shared = (jnp.zeros((S, arch["kv_heads"],
                                         arch["head_dim"]), jnp.float32),) * 2
                x, memory, shared = _layer_jit(
                    x, lp, memory, shared, jnp.float32(layer),
                    jnp.int32(step), kind=kind, arch=arch)
            rows.append(x)
        x = jnp.stack(rows)
        if at is not None:
            x = x[:, jnp.asarray(at)]
            S = x.shape[1]
        x = _layer_norm(x, _f32(params["final_norm"]), arch["eps"])
        head = params["lm_head"] if "lm_head" in params else emb.T
        V = head.shape[1]
        out = jnp.zeros((B, S, V), jnp.float32)
        for lo in range(0, V, VOCAB_BLOCK):
            out = _head_slice(out, x, head[:, lo:lo + VOCAB_BLOCK], lo)
        return out
