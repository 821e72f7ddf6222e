"""Plain reference: the two dense decoder families of the benchmark's
configurations, forward pass and next-token loss, in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.

Written from the published model descriptions (Hugging Face
``modeling_mistral.py`` / ``modeling_gpt_neox.py``), not from the program:
it imports nothing of ``deepspeed_tpu``. It reads the *layout* of the
program's parameter tree (stacked ``blocks`` with ``wq wk wv wo w_up w_gate
w_down``, ``ln1 ln2 final_norm`` as ``{"scale", "bias"}``, ``tok_emb``,
``lm_head``; projection matrices stored ``[in, out]``) because the
weights under test are the program's own. No kernel, no cache, no scan,
no batching tricks: a Python loop over layers that upcasts one layer at a
time, and attention as a masked softmax computed in blocks of queries.

Departures from the sources, each deliberate:
* ``gpt_neox``: the fused ``query_key_value`` matrix is read as the three
  matrices the program keeps (the same linear map, a different storage
  order);
* weights are whatever the caller passes, upcast to float32 (the program's
  bfloat16 weights for serving; its float32 master rounded to bfloat16,
  which is what its step computes with, for training).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 1024


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the configuration file's
    ``model_type`` and the source keys as run (``hf``)."""
    family = config["model_type"]
    heads = hf["num_attention_heads"]
    head_dim = hf["hidden_size"] // heads
    if family == "mistral":
        return dict(family=family, heads=heads,
                    kv_heads=hf["num_key_value_heads"], head_dim=head_dim,
                    eps=hf["rms_norm_eps"], theta=float(hf["rope_theta"]),
                    rotary_dim=head_dim, window=hf.get("sliding_window"))
    if family == "gpt_neox":
        rot = int(head_dim * hf["rotary_pct"])
        return dict(family=family, heads=heads, kv_heads=heads,
                    head_dim=head_dim, eps=hf["layer_norm_eps"],
                    theta=float(hf["rotary_emb_base"]),
                    rotary_dim=rot - rot % 2, window=None,
                    parallel=bool(hf["use_parallel_residual"]))
    raise ValueError(f"no reference for model_type {family!r}")


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, positions, rotary_dim, theta):
    """x [B, S, N, D]; rotates the first ``rotary_dim`` dims of each head
    (``rotate_half`` convention of both sources)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [B, S, r/2]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]  # [B,S,1,r]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = rot * jnp.cos(emb) + _rotate_half(rot) * jnp.sin(emb)
    return jnp.concatenate([rot, rest], axis=-1)


def _attention(q, k, v, window):
    """Causal softmax attention. q [B,S,N,D]; k, v [B,S,K,D]."""
    B, S, N, D = q.shape
    rep = N // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[1])
        s = jnp.einsum("bqnd,bknd->bnqk", qb, k) / math.sqrt(D)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bnqk,bknd->bqnd",
                               jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=1)


def _layer(x, lp, positions, arch):
    lp = _f32(lp)          # one layer of the caller's weights, upcast here
    B, S, H = x.shape
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    neox = arch["family"] == "gpt_neox"
    norm = _layer_norm if neox else _rms_norm

    def lin(inp, w, b):
        out = inp @ lp[w]
        return out + lp[b] if neox else out

    h = norm(x, lp["ln1"], arch["eps"])
    q = lin(h, "wq", "bq").reshape(B, S, N, D)
    k = lin(h, "wk", "bk").reshape(B, S, K, D)
    v = lin(h, "wv", "bv").reshape(B, S, K, D)
    q = _rope(q, positions, arch["rotary_dim"], arch["theta"])
    k = _rope(k, positions, arch["rotary_dim"], arch["theta"])
    attn = _attention(q, k, v, arch["window"]).reshape(B, S, N * D)
    attn = lin(attn, "wo", "bo")
    if neox:
        src = x if arch["parallel"] else x + attn
        h2 = norm(src, lp["ln2"], arch["eps"])
        mlp = lin(jax.nn.gelu(lin(h2, "w_up", "b_up"), approximate=False),
                  "w_down", "b_down")
        return x + attn + mlp
    x = x + attn
    h2 = norm(x, lp["ln2"], arch["eps"])
    return x + (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])) \
        @ lp["w_down"]


_layer_jit = jax.jit(_layer, static_argnames=("arch",))


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def forward_logits(params, tokens, arch: Dict[str, Any]):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
        depth = jax.tree.leaves(params["blocks"])[0].shape[0]
        for layer in range(depth):
            lp = jax.tree.map(lambda a: a[layer], params["blocks"])
            x = _layer_jit(x, lp, positions, arch=arch)
        fnorm = _layer_norm if arch["family"] == "gpt_neox" else _rms_norm
        x = fnorm(x, _f32(params["final_norm"]), arch["eps"])
        return x @ jnp.asarray(params["lm_head"], jnp.float32)


def next_token_loss(params, tokens, arch: Dict[str, Any]) -> float:
    """Mean cross-entropy of token t+1 given tokens <= t, over every
    position of every sequence, one sequence at a time."""
    total, count = 0.0, 0
    tokens = jnp.asarray(tokens)
    for row in range(tokens.shape[0]):
        logits = forward_logits(params, tokens[row:row + 1], arch)[0, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = tokens[row, 1:]
        total += float(-jnp.take_along_axis(logp, tgt[:, None], axis=1).sum())
        count += int(tgt.shape[0])
    return total / count
