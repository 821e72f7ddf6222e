"""Plain reference: the ``afmoe`` family's decoder (Arcee Trinity:
``model_type`` ``afmoe``), forward pass and next-token loss, in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.

Written from the family's published modelling code as the builder knows it
(``modeling_afmoe.py``: ``AfmoeDecoderLayer``, ``AfmoeAttention``,
``AfmoeTokenChoiceRouter``, ``AfmoeMoE``), not from the program: it
imports nothing of ``deepspeed_tpu``. The equations:

* ``h0 = E[token] * sqrt(d)`` (``mup_enabled``);
* every layer, four RMSNorms: ``h += N2(Attn(N1 h))``; ``h += N4(F(N3 h))``;
* attention: ``q, k, v, g`` projected from the normed input; ``q`` and
  ``k`` RMS-normed per head with a learned gain; a SLIDING layer rotates
  ``q`` and ``k`` (whole head, half-split pairs, unscaled) and sees
  positions ``i - window < j <= i``; a FULL layer applies no rotary at all
  and sees ``j <= i``; grouped queries, scale ``head_dim ** -0.5``;
  ``out = Wo (o * sigmoid(g))``, the gate elementwise over every value;
* ``F``: a SwiGLU MLP in the first ``num_dense_layers`` layers; after
  them ``Shared(u) + sum_e w_e Expert_e(u)`` with ``s = sigmoid(Wr u)`` in
  float32, the experts chosen the top-k of ``s + expert_bias``, the weights
  ``s[chosen] / (sum + 1e-20) * route_scale``;
* final RMSNorm, ``logits = H h``.

It reads the *layout* of the program's parameter tree (``dense_blocks``
then ``blocks``, leaves stacked by layer; ``ln1 ln1_post ln2 ln2_post``
the four norms in order; ``wq wk wv wo wg q_norm k_norm``; ``w_gate w_up
w_down`` for a dense FFN and, with a leading expert axis, for the routed
experts; ``sw_*`` the shared expert; ``gate_w gate_bias`` the router;
matrices ``[in, out]``) because the weights under test are the program's.
No kernel, no cache, no ring, no sort or grouped matmul: an explicit
``[queries, S]`` mask per layer kind, a plain top-k, and a loop over the
experts in which every expert sees every token, weighted by the token's
routing weight for it (zero for most).

A SHARE of the expert layers (the ``model-configs`` guide, section 4) is
given as the program is given it: the router is as wide as the published
count of experts and chooses among all of them; the loop runs over the
experts the parameter tree HOLDS (``num_experts`` from ``first_expert``),
so what the absent experts would have added is left out here as there; the
logits are over the rows of the head that are held.

Departures, each deliberate: queries are met a block at a time
(``Q_BLOCK`` rows against every key: 6.4k positions fit beside a serving
engine); the head is applied a slice of the vocabulary at a time into one
buffer; weights are upcast to float32 a layer (an expert) at a time;
"depth-scaled" sandwich norms are an initialisation of N2 and N4's gains
and change nothing of the equations.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 64
VOCAB_BLOCK = 8192

_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "afmoe":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    if hf.get("rope_scaling") or hf.get("n_group", 1) != 1 \
            or hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("reference: unscaled rotary, one routing group and "
                         "sigmoid scores are what is written")
    kinds = tuple(_KINDS[t] for t in hf["layer_types"])
    assert len(kinds) == hf["num_hidden_layers"], "layer_types vs depth"
    return dict(
        kinds=kinds, dense_layers=int(hf["num_dense_layers"]),
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        window=int(hf["sliding_window"]), eps=hf["rms_norm_eps"],
        theta=float(hf["rope_theta"]), top_k=hf["num_experts_per_tok"],
        route_norm=bool(hf.get("route_norm", True)),
        route_scale=float(hf.get("route_scale", 1.0)),
        emb_mult=float(hf["hidden_size"]) ** 0.5
        if hf.get("mup_enabled") else 1.0,
        first_expert=int(hf.get("first_expert", 0)))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


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


def _attention(q, k, v, window: int):
    """q [S, K, rep, D]; k, v [S, K, D]: causal softmax attention, under a
    window where ``window`` > 0, a block of queries at a time against every
    key under an explicit mask."""
    S, K, rep, D = q.shape
    pad = -S % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(args):
        qb, lo = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)  # pad rows
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        s = jnp.einsum("qkrd,skd->krqs", qb, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("krqs,skd->qkrd", p, v)

    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, K, rep, D),
                              jnp.arange(0, S + pad, Q_BLOCK)))
    return out.reshape(S + pad, K * rep * D)[:S]


def _attn(u, lp, arch, kind: str):
    S = u.shape[0]
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    q = _rms_norm((u @ lp["wq"]).reshape(S, N, D), lp["q_norm"], arch["eps"])
    k = _rms_norm((u @ lp["wk"]).reshape(S, K, D), lp["k_norm"], arch["eps"])
    v = (u @ lp["wv"]).reshape(S, K, D)
    if kind == "sliding":
        q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
    o = _attention(q.reshape(S, K, N // K, D), k, v,
                   arch["window"] if kind == "sliding" else 0)
    return (o * jax.nn.sigmoid(u @ lp["wg"])) @ lp["wo"]


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _route(u, lp, arch):
    """[T, H] -> (routing weight of every token for every expert of the
    ROUTER [T, E], zero outside its top-k; the experts chosen [T, k])."""
    scores = jax.nn.sigmoid(u @ lp["gate_w"])
    _, idx = jax.lax.top_k(scores + lp["gate_bias"], arch["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if arch["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * arch["route_scale"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot), idx


def _moe(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: the routed experts
    HELD, every expert layer's ``[layers, held, in, out]`` as passed, of
    which ``layer`` is this one's (one expert's matrices are read and
    upcast at a time: a layer's share is 1.7 GB at the published widths)."""
    weight, chosen = _route(u, lp, arch)                      # [T, E]
    held = stack["w_up"].shape[1]
    weight = jax.lax.dynamic_slice_in_dim(
        weight, arch["first_expert"], held, axis=1)

    def one_expert(e, y):
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return y + we * _mlp(u, w_gate, w_up, w_down)

    y = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))
    y = y + _mlp(u, lp["sw_gate"], lp["sw_up"], lp["sw_down"])
    return y, chosen


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(x, lp, stack, layer, arch, kind: str):
    """x [S, H] of one sequence; ``stack``: None for a dense layer.
    Returns (x, the experts every position chose [S, k]; None where dense)."""
    lp = _f32(lp)
    eps = arch["eps"]
    a = _attn(_rms_norm(x, lp["ln1"]["scale"], eps), lp, arch, kind)
    x = x + _rms_norm(a, lp["ln1_post"]["scale"], eps)
    u = _rms_norm(x, lp["ln2"]["scale"], eps)
    f, chosen = _moe(u, lp, stack, layer, arch) if stack is not None \
        else (_mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None)
    x = x + _rms_norm(f, lp["ln2_post"]["scale"], eps)
    return x, chosen


_layer_jit = jax.jit(_layer, static_argnames=("arch", "kind"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, x @ w.astype(jnp.float32), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def forward_logits(params, tokens, arch: Dict[str, Any], at=None,
                   routes=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32 over the rows of
    the head that ``params`` holds; with ``at`` (a list of positions) the
    logits of those positions alone, [B, len(at), V]. ``routes``: a list
    that receives, for every sequence and expert layer in turn, the experts
    of the ROUTER that each (``at``) position chose, [positions, k]."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        emb = jnp.asarray(params["tok_emb"])
        rows = []
        for b in range(tokens.shape[0]):
            x = emb[tokens[b]].astype(jnp.float32) * arch["emb_mult"]
            index = 0
            for key, experts in (("dense_blocks", False), ("blocks", True)):
                if key not in params:
                    continue
                depth = jax.tree.leaves(params[key])[0].shape[0]
                stack = {k: params[key][k] for k in _EXPERT_LEAVES} \
                    if experts else None
                for layer in range(depth):
                    lp = jax.tree.map(
                        lambda a: a[layer],
                        {k: v for k, v in params[key].items()
                         if not (experts and k in _EXPERT_LEAVES)})
                    x, chosen = _layer_jit(x, lp, stack, layer, arch=arch,
                                           kind=arch["kinds"][index])
                    if routes is not None and experts:
                        routes.append(chosen if at is None
                                      else chosen[jnp.asarray(at)])
                    index += 1
            assert index == len(arch["kinds"]), "depth vs layer_types"
            rows.append(x if at is None else x[jnp.asarray(at)])
        x = _rms_norm(jnp.stack(rows), jnp.asarray(
            params["final_norm"]["scale"], jnp.float32), arch["eps"])
        head = params["lm_head"] if "lm_head" in params \
            else jnp.asarray(params["tok_emb"]).T
        V = head.shape[1]
        out = jnp.zeros(x.shape[:2] + (V,), jnp.float32)
        for lo in range(0, V, VOCAB_BLOCK):
            out = _head_slice(out, x, head[:, lo:lo + VOCAB_BLOCK], lo)
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
