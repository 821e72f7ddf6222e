"""Plain reference: the ``kimi_linear`` family's decoder (Kimi-Linear:
delta-rule linear-attention layers, three to every latent-attention layer
without rotary, over one leading dense layer and layers of routed experts
beside a shared one), forward pass and next-token loss, in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.

Written from the family's published description as ISSUE 41 sets it out
(the Kimi Linear paper's Kimi Delta Attention and the family's modelling
code), not from the program: it imports nothing of ``deepspeed_tpu``. The
equations (``x`` the residual stream, no bias anywhere, no positions
anywhere):

* every layer: ``x += mixer(N1 x)``, then ``x += ffn(N2 x)``, RMSNorm with
  eps ``rms_norm_eps``; after the last layer one RMSNorm, then the untied
  head;
* ``kda`` mixer on the normed ``u [S, H]``: ``q~, k~, v~ = u W_q, u W_k,
  u W_v``; each through its own depthwise causal convolution of
  ``short_conv_kernel_size`` taps (the last tap on the row itself, zeros
  before the sequence's start), then SiLU; per head ``q = l2norm(q) /
  sqrt(D)``, ``k = l2norm(k)`` (``x / sqrt(sum x^2 + 1e-6)``); the decay a
  head and CHANNEL ``a = exp(-exp(A_log_h) softplus((u W_fa) W_fb +
  dt_bias))``; the step size ``b = sigmoid(u W_b)``, one a head; from a
  zero state ``S [D keys, D values]`` a head, one row after another:
  ``S' = diag(a_t) S``; ``S = S' + b_t k_t (v_t - S'^T k_t)^T``; ``o_t =
  S^T q_t``; ``y = RMSNorm_D(o_t) * sigmoid((u W_ga) W_gb)`` (one gain
  over each head's D); then ``W_o``;
* ``latent`` mixer: ``q = u W_q`` as heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``[c | k_pe] = u W_kva``, ``c`` RMS-normed; per head
  ``k = [c W_kb | k_pe]``, ``v = c W_vb``; causal softmax of ``q k^T /
  sqrt(192)``; NO rotation of the ``qk_rope_head_dim`` values
  (``mla_use_nope``); ``W_o``;
* ``ffn`` of the first ``first_k_dense_replace`` layers: ``W_d (silu(W_g
  u) * W_u u)``; of the others ``s = sigmoid(u W_r)`` over ALL the model's
  experts in float32; the ``num_experts_per_token`` largest of ``s +
  correction bias`` are chosen (one group); weights ``s`` at the chosen,
  over their sum (``moe_renormalize``), ``* routed_scaling_factor``; the
  weighted sum of the chosen experts' SwiGLUs, of which only those HELD
  here (``num_experts`` from ``first_expert``) are added; plus the shared
  expert's SwiGLU.

It reads the *layout* of the program's parameter tree (``dense_blocks``
then ``blocks``; ``ln1`` / ``ln2``, the FFN's or the router's, the shared
expert's and the experts' leaves stacked by layer; ``kda: {wq wk wv conv_q
conv_k conv_v [taps, N D] w_fa w_fb dt_bias a_log w_b w_ga w_gb o_norm
wo}`` stacked over the segment's ``kda`` layers and ``attn: {wq wkv_a
kv_a_norm wkv_b wo}`` over its latent layers; matrices ``[in, out]``)
because the weights under test are the program's. No kernel, no cache, no
chunks, no sort or grouped matmul.

``arch["faults"]`` (empty but in the probes and tests that make a mistake
on purpose) names equations to get wrong: ``decay-dropped``, ``b-is-one``,
``taps-reversed``, ``no-l2norm``, ``rotary-on-latent``.

Departures, each deliberate: queries are met a block at a time, the head a
slice of the vocabulary at a time, weights upcast a layer (an expert) at a
time: so the check fits beside a serving engine.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 64
VOCAB_BLOCK = 8192
L2_EPS = 1e-6

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
_MIXERS = {"kda": "kda", "latent": "attn"}


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "kimi_linear":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    la = hf["linear_attn_config"]
    depth = hf["num_hidden_layers"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    assert not kda & full and kda | full == set(range(1, depth + 1)), \
        "the two layer lists name every layer 1 .. depth once"
    if hf.get("q_lora_rank") or hf.get("rope_scaling") \
            or not hf.get("mla_use_nope") \
            or hf.get("num_expert_group", 1) != 1 \
            or hf.get("moe_router_activation_func") != "sigmoid":
        raise ValueError("reference: direct queries, no rotary, one "
                         "routing group and sigmoid scores are written")
    return dict(
        kinds=tuple("kda" if i in kda else "latent"
                    for i in range(1, depth + 1)),
        heads=hf["num_attention_heads"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], v_dim=hf["v_head_dim"],
        kv_rank=hf["kv_lora_rank"], kda_heads=la["num_heads"],
        kda_dim=la["head_dim"], taps=la["short_conv_kernel_size"],
        eps=hf["rms_norm_eps"], top_k=hf["num_experts_per_token"],
        route_norm=bool(hf.get("moe_renormalize", True)),
        route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        first_expert=int(hf.get("first_expert", 0)),
        theta=float(hf.get("rope_theta", 10000.0)), faults=frozenset())


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every linear layer of the model: projections, FFNs, the router, the
    head (one place, so that a probe can read the whole reference in a
    lower precision: ``tools/kimi_linear_probe.py``)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _short_conv(x, taps, reverse: bool):
    """x [S, C] through a depthwise causal convolution, taps [n, C]: explicit
    shifts by 1 .. n-1 rows, zeros before the sequence's start; tap n-1
    meets the row itself."""
    n = taps.shape[0]
    if reverse:
        taps = taps[::-1]
    out = taps[n - 1] * x
    for back in range(1, n):
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype),
                                   x[:-back]])[:x.shape[0]]
        out = out + taps[n - 1 - back] * shifted
    return out


def _kda(u, lp, arch):
    S = u.shape[0]
    N, D, faults = arch["kda_heads"], arch["kda_dim"], arch["faults"]

    def branch(x):
        y = _short_conv(_linear(u, lp[f"w{x}"]), lp[f"conv_{x}"],
                        "taps-reversed" in faults)
        return jax.nn.silu(y).reshape(S, N, D)

    q, k, v = branch("q"), branch("k"), branch("v")
    if "no-l2norm" not in faults:
        q, k = _l2norm(q), _l2norm(k)
    q = q * D ** -0.5
    a = jnp.exp(-jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(
        _linear(_linear(u, lp["w_fa"]), lp["w_fb"]) + lp["dt_bias"]
    ).reshape(S, N, D))
    if "decay-dropped" in faults:
        a = jnp.ones_like(a)
    b = jax.nn.sigmoid(_linear(u, lp["w_b"]))                   # [S, N]
    if "b-is-one" in faults:
        b = jnp.ones_like(b)

    def row(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = a_t[:, :, None] * state                    # [N, keys, values]
        seen = jnp.einsum("nk,nkv->nv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    _, o = jax.lax.scan(row, jnp.zeros((N, D, D), jnp.float32),
                        (q, k, v, a, b))
    gate = jax.nn.sigmoid(_linear(_linear(u, lp["w_ga"]), lp["w_gb"]))
    y = _rms_norm(o, lp["o_norm"], arch["eps"]).reshape(S, N * D) * gate
    return _linear(y, lp["wo"])


def _rope(x, theta):
    """A fault made on purpose (``rotary-on-latent``): x [S, n, d] rotated
    at positions 0 .. S-1, pairs split by halves."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(q, k, v, scale):
    """q, k [S, N, Dk]; v [S, N, Dv]: causal softmax attention, a block of
    queries at a time against every key under an explicit mask."""
    S, N, _ = q.shape
    pad = -S % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(args):
        qb, lo = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)  # pad rows
        seen = kpos[None, :] <= qpos[:, None]
        s = jnp.einsum("qnd,snd->nqs", qb, k) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nqs,snd->qnd", p, v)

    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, N, q.shape[2]),
                              jnp.arange(0, S + pad, Q_BLOCK)))
    return out.reshape(S + pad, -1)[:S]


def _latent(u, lp, arch):
    S = u.shape[0]
    N, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v_dim"]
    q = _linear(u, lp["wq"]).reshape(S, N, dn + dr)
    kv_a = _linear(u, lp["wkv_a"])
    c = _rms_norm(kv_a[:, :arch["kv_rank"]], lp["kv_a_norm"], arch["eps"])
    k_pe = kv_a[:, None, arch["kv_rank"]:]                      # [S, 1, dr]
    if "rotary-on-latent" in arch["faults"]:
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], arch["theta"])],
                            axis=-1)
        k_pe = _rope(k_pe, arch["theta"])
    kv = _linear(c, lp["wkv_b"]).reshape(S, N, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, N, dr))],
                        axis=-1)
    return _linear(_attention(q, k, kv[..., dn:], (dn + dr) ** -0.5),
                   lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return _linear(jax.nn.silu(_linear(x, w_gate)) * _linear(x, w_up),
                   w_down)


def _route(u, lp, arch):
    """[T, H] -> (routing weight of every token for every expert of the
    MODEL [T, E], zero outside its top-k; the experts chosen [T, k])."""
    scores = jax.nn.sigmoid(_linear(u, lp["gate_w"]))
    _, idx = jax.lax.top_k(scores + lp["gate_bias"], arch["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if arch["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * arch["route_scale"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot), idx


def _moe(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: every expert layer's
    ``[layers, experts held, in, out]`` as passed, of which ``layer`` is
    this one's and expert ``e`` the model's ``first_expert + e`` (one
    expert's matrices are read and upcast at a time)."""
    weight, chosen = _route(u, lp, arch)                      # [T, E]

    def one_expert(e, y):
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(
            weight, arch["first_expert"] + e, 1, axis=1)
        return y + we * _mlp(u, w_gate, w_up, w_down)

    y = jax.lax.fori_loop(0, stack["w_up"].shape[1], one_expert,
                          jnp.zeros_like(u))
    return y + _mlp(u, lp["sw_gate"], lp["sw_up"], lp["sw_down"]), chosen


def _layer(x, lp, stack, layer, arch, kind: str):
    """x [S, H] of one sequence; ``lp``: the layer's norms, its mixer's
    leaves and its FFN's or router's, flat; ``stack``: None for a dense
    layer. Returns (x, the experts every position chose [S, k]; None where
    dense)."""
    lp = _f32(lp)
    u = _rms_norm(x, lp["ln1"]["scale"], arch["eps"])
    x = x + (_kda if kind == "kda" else _latent)(u, lp, arch)
    u = _rms_norm(x, lp["ln2"]["scale"], arch["eps"])
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
                seen = {"attn": 0, "kda": 0}
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
            assert index == len(arch["kinds"]), "depth vs the layer lists"
            rows.append(x if at is None else x[jnp.asarray(at)])
        x = _rms_norm(jnp.stack(rows), jnp.asarray(
            params["final_norm"]["scale"], jnp.float32), arch["eps"])
        head = jnp.asarray(params["lm_head"])
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
