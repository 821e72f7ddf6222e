"""Plain reference: the ``lfm2_moe`` family's decoder (Liquid LFM2-MoE:
gated short-convolution layers among grouped-query attention layers, over
a leading run of dense layers and layers of routed experts), forward pass
and next-token loss, in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.

Written from the family's published description as ISSUE 37 sets it out
(``modeling_lfm2_moe.py``: ``Lfm2MoeDecoderLayer``, ``Lfm2MoeShortConv``,
``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``), not from the program: it
imports nothing of ``deepspeed_tpu``. The equations (``x`` the residual
stream, no bias anywhere):

* every layer: ``x += mixer(N_op x)``, then ``x += ffn(N_ffn x)``, RMSNorm
  with eps ``norm_eps``; after the last layer one RMSNorm
  (``embedding_norm``), then the head, the embedding transposed;
* ``conv`` mixer on the normed ``u [S, H]``: ``[B | C | z] = u W_in``;
  ``g = B * z``; ``c_t = w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t`` per
  channel (``conv_L_cache`` 3 taps, zeros before the sequence's start);
  ``y = (C * c) W_out``. No activation, no scan;
* ``full_attention`` mixer: ``q, k, v`` projected; ``q`` and ``k``
  RMS-normed over each head's values with a learned gain, THEN rotary
  (theta ``rope_theta``, the whole head, pairs split by halves, unscaled);
  causal softmax of ``q k^T / sqrt(head_dim)``, grouped queries; ``W_o``;
* ``ffn`` of the first ``num_dense_layers`` layers: ``W2 (silu(W1 u) *
  W3 u)``; of the others ``s = sigmoid(u W_r)``; the ``num_experts_per_tok``
  largest of ``s + expert_bias`` are chosen; weights ``s`` at the chosen,
  ``/ (their sum + 1e-6)``, ``* routed_scaling_factor``; the weighted sum
  of the chosen experts' SwiGLUs.

It reads the *layout* of the program's parameter tree (``dense_blocks``
then ``blocks``; ``ln1`` / ``ln2`` the two norms and the FFN's or the
experts' leaves stacked by layer; ``conv: {w_in, conv_w [taps, H], wo}``
stacked over the segment's ``conv`` layers and ``attn: {wq wk wv wo q_norm
k_norm}`` over its attention layers; matrices ``[in, out]``) because the
weights under test are the program's. No kernel, no cache, no state, no
sort or grouped matmul: the convolution is two explicit shifts of ``g``,
the mask an explicit ``[queries, S]`` one, the experts a loop over all of
them in which every expert sees every token, weighted by the token's
routing weight for it (zero for most).

Departures, each deliberate: queries are met a block at a time
(``Q_BLOCK`` rows against every key), the head is applied a slice of the
vocabulary at a time into one buffer, and weights are upcast to float32 a
layer (an expert) at a time: so the check fits beside a serving engine.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 64
VOCAB_BLOCK = 8192

_KINDS = {"conv": "conv", "full_attention": "full"}
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
_MIXERS = {"conv": "conv", "full": "attn"}


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "lfm2_moe":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    rope = hf.get("rope_parameters") or {}
    if hf.get("conv_bias") or rope.get("rope_type", "default") != "default":
        raise ValueError("reference: a convolution without bias and "
                         "unscaled rotary are what is written")
    kinds = tuple(_KINDS[t] for t in hf["layer_types"])
    assert len(kinds) == hf["num_hidden_layers"], "layer_types vs depth"
    return dict(
        kinds=kinds, dense_layers=int(hf["num_dense_layers"]),
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        taps=int(hf["conv_L_cache"]), eps=hf["norm_eps"],
        theta=float(rope.get("rope_theta", 1000000.0)),
        top_k=hf["num_experts_per_tok"],
        route_norm=bool(hf.get("norm_topk_prob", True)),
        route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        expert_bias=bool(hf.get("use_expert_bias", True)))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every linear layer of the model: projections, FFNs, the router, the
    head (one place, so that a probe can read the whole reference in a
    lower precision: ``tools/lfm2_probe.py``)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [S, n, d] at positions 0 .. S-1: every dim rotates, pairs split by
    halves (``rotate_half``)."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # [S, d/2]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(q, k, v):
    """q [S, K, rep, D]; k, v [S, K, D]: causal softmax attention, a block
    of queries at a time against every key under an explicit mask."""
    S, K, rep, D = q.shape
    pad = -S % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(args):
        qb, lo = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)  # pad rows
        seen = kpos[None, :] <= qpos[:, None]
        s = jnp.einsum("qkrd,skd->krqs", qb, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("krqs,skd->qkrd", p, v)

    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, K, rep, D),
                              jnp.arange(0, S + pad, Q_BLOCK)))
    return out.reshape(S + pad, K * rep * D)[:S]


def _attn(u, lp, arch):
    S = u.shape[0]
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    q = _rms_norm(_linear(u, lp["wq"]).reshape(S, N, D), lp["q_norm"],
                  arch["eps"])
    k = _rms_norm(_linear(u, lp["wk"]).reshape(S, K, D), lp["k_norm"],
                  arch["eps"])
    v = _linear(u, lp["wv"]).reshape(S, K, D)
    q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
    return _linear(_attention(q.reshape(S, K, N // K, D), k, v), lp["wo"])


def _short_conv(u, lp, arch):
    """The gated short convolution: explicit shifts of ``g`` by 1 .. taps-1
    rows, zeros before the sequence's start; tap ``taps - 1`` meets the
    row itself."""
    H, taps = u.shape[1], arch["taps"]
    bcz = _linear(u, lp["w_in"])
    b, c, z = bcz[:, :H], bcz[:, H:2 * H], bcz[:, 2 * H:]
    g = b * z
    conv = lp["conv_w"][taps - 1] * g
    for back in range(1, taps):
        shifted = jnp.concatenate([jnp.zeros((back, H), g.dtype),
                                   g[:-back]])[:g.shape[0]]
        conv = conv + lp["conv_w"][taps - 1 - back] * shifted
    return _linear(c * conv, lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return _linear(jax.nn.silu(_linear(x, w_gate)) * _linear(x, w_up),
                   w_down)


def _route(u, lp, arch):
    """[T, H] -> (routing weight of every token for every expert [T, E],
    zero outside its top-k; the experts chosen [T, k])."""
    scores = jax.nn.sigmoid(_linear(u, lp["gate_w"]))
    select = scores + lp["gate_bias"] if arch["expert_bias"] else scores
    _, idx = jax.lax.top_k(select, arch["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if arch["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * arch["route_scale"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot), idx


def _moe(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: every expert
    layer's ``[layers, experts, in, out]`` as passed, of which ``layer`` is
    this one's (one expert's matrices are read and upcast at a time: a
    layer's experts are 1.2 GB at the published widths)."""
    weight, chosen = _route(u, lp, arch)                      # [T, E]

    def one_expert(e, y):
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return y + we * _mlp(u, w_gate, w_up, w_down)

    y = jax.lax.fori_loop(0, stack["w_up"].shape[1], one_expert,
                          jnp.zeros_like(u))
    return y, chosen


def _layer(x, lp, stack, layer, arch, kind: str):
    """x [S, H] of one sequence; ``lp``: the layer's norms, its mixer's
    leaves and its FFN's or router's, flat; ``stack``: None for a dense
    layer. Returns (x, the experts every position chose [S, k]; None where
    dense)."""
    lp = _f32(lp)
    eps = arch["eps"]
    u = _rms_norm(x, lp["ln1"]["scale"], eps)
    x = x + (_short_conv(u, lp, arch) if kind == "conv"
             else _attn(u, lp, arch))
    u = _rms_norm(x, lp["ln2"]["scale"], eps)
    f, chosen = _moe(u, lp, stack, layer, arch) if stack is not None \
        else (_mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None)
    return x + f, chosen


_layer_jit = jax.jit(_layer, static_argnames=("arch", "kind"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, _linear(x, w.astype(jnp.float32)), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _layer_params(blocks, experts: bool, layer: int, nth: int, mixer: str):
    """The leaves of layer ``layer`` of a segment, flat: those stacked by
    layer and those of its mixer, which is the ``nth`` of its kind."""
    lp = jax.tree.map(
        lambda a: a[layer],
        {k: v for k, v in blocks.items()
         if k not in _MIXERS.values()
         and not (experts and k in _EXPERT_LEAVES)})
    lp.update(jax.tree.map(lambda a: a[nth], blocks[mixer]))
    return lp


def forward_logits(params, tokens, arch: Dict[str, Any], at=None,
                   routes=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with ``at`` (a list
    of positions) the logits of those positions alone, [B, len(at), V].
    ``routes``: a list that receives, for every sequence and expert layer
    in turn, the experts each (``at``) position chose, [positions, k]."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        emb = jnp.asarray(params["tok_emb"])
        rows = []
        for b in range(tokens.shape[0]):
            x = emb[tokens[b]].astype(jnp.float32)
            index = 0
            for key, experts in (("dense_blocks", False), ("blocks", True)):
                if key not in params:
                    continue
                depth = params[key]["ln1"]["scale"].shape[0]
                stack = {k: params[key][k] for k in _EXPERT_LEAVES} \
                    if experts else None
                seen = {"attn": 0, "conv": 0}
                for layer in range(depth):
                    kind = arch["kinds"][index]
                    mixer = _MIXERS[kind]
                    lp = _layer_params(params[key], experts, layer,
                                       seen[mixer], mixer)
                    seen[mixer] += 1
                    x, chosen = _layer_jit(x, lp, stack, layer, arch=arch,
                                           kind=kind)
                    if routes is not None and experts:
                        routes.append(chosen if at is None
                                      else chosen[jnp.asarray(at)])
                    index += 1
            assert index == len(arch["kinds"]), "depth vs layer_types"
            rows.append(x if at is None else x[jnp.asarray(at)])
        x = _rms_norm(jnp.stack(rows), jnp.asarray(
            params["final_norm"]["scale"], jnp.float32), arch["eps"])
        # the tied head a slice of the embedding's rows at a time (the
        # whole of it transposed is a quarter of a gigabyte beside an
        # engine that leaves half of one)
        tied = "lm_head" not in params
        head = jnp.asarray(params["tok_emb" if tied else "lm_head"])
        V = head.shape[0 if tied else 1]
        out = jnp.zeros(x.shape[:2] + (V,), jnp.float32)
        for lo in range(0, V, VOCAB_BLOCK):
            w = head[lo:lo + VOCAB_BLOCK].T if tied \
                else head[:, lo:lo + VOCAB_BLOCK]
            out = _head_slice(out, x, w, lo)
        return out


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
