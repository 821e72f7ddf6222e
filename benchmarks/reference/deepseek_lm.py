"""Plain reference: the DeepSeek-V3 family's decoder (``model_type``
``deepseek_v3``: latent attention, a leading run of dense layers, then
layers of routed experts beside shared ones), forward pass and next-token
loss, in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.

Written from the published description (Hugging Face
``modeling_deepseek_v3.py``: ``DeepseekV3Attention``, ``DeepseekV3MoE``,
``DeepseekV3TopkRouter``), not from the program: it imports nothing of
``deepspeed_tpu``. It reads the *layout* of the program's parameter tree
(``dense_blocks`` then ``blocks``, leaves stacked by layer; ``wq wkv_a
kv_a_norm wkv_b wo`` for attention, ``w_gate w_up w_down`` for a dense
layer's FFN and, with a leading expert axis, for the routed experts,
``sw_gate sw_up sw_down`` for the shared experts, ``gate_w gate_bias`` for
the router; matrices stored ``[in, out]``) because the weights under test
are the program's own. No kernel, no cache, no weight absorption, no sort
or grouped matmul: keys and values are expanded per head as the paper
writes them, the router is a plain top-k, and the experts are a loop in
which every expert sees every token and its result is weighted by the
token's routing weight for it (zero for most).

Departures from the source, each deliberate:
* ``q_lora_rank`` null only (the configuration this file was written for;
  a low-rank query raises);
* ``n_group = topk_group = 1`` only: with one group the source's
  group-limited selection is the plain top-k written here (asserted);
* the shared experts' ``n_shared_experts`` MLPs are one MLP of
  ``n_shared_experts x moe_intermediate_size`` columns, as the source
  itself builds them;
* the output head is applied a slice of the vocabulary at a time into one
  buffer (164k columns in float32 beside a deployment do not fit twice);
* weights are whatever the caller passes, upcast to float32 a layer (an
  expert) at a time.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 512
VOCAB_BLOCK = 16384


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "deepseek_v3":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    if hf.get("q_lora_rank"):
        raise ValueError("reference: q_lora_rank must be null")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("reference: group-limited routing is not written")
    if hf.get("rope_scaling"):
        raise ValueError("reference: rope_scaling is not written")
    return dict(
        heads=hf["num_attention_heads"], kv_rank=hf["kv_lora_rank"],
        nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
        v_dim=hf["v_head_dim"], eps=hf["rms_norm_eps"],
        theta=float(hf["rope_theta"]), top_k=hf["num_experts_per_tok"],
        norm_topk=bool(hf["norm_topk_prob"]),
        route_scale=float(hf["routed_scaling_factor"]),
        sigmoid=hf.get("scoring_func", "sigmoid") == "sigmoid",
        # DeepseekV3 stores each rotary pair interleaved (re, im, re, im)
        interleave=bool(hf.get("rope_interleave", True)))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, positions, theta, interleave):
    """x [B, S, n, d]: every dim rotates. The source permutes interleaved
    pairs to the half-split layout first
    (``apply_rotary_pos_emb_interleave``), then rotates by halves."""
    d = x.shape[-1]
    if interleave:
        x = x.reshape(x.shape[:-1] + (d // 2, 2))
        x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv       # [B, S, d/2]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attention(q, k, v):
    """Causal softmax attention, queries in blocks. q, k [B,S,N,dk];
    v [B,S,N,dv]."""
    S, dk = q.shape[1], q.shape[3]
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[1])
        s = jnp.einsum("bqnd,bknd->bnqk", qb, k) / math.sqrt(dk)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s,
                      -jnp.inf)
        outs.append(jnp.einsum("bnqk,bknd->bqnd",
                               jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=1)


def _mla(x, lp, positions, arch):
    """Multi-head latent attention as the paper writes it: the latent is
    expanded to per-head keys and values (nothing absorbed, nothing cached)."""
    B, S, _ = x.shape
    N, r = arch["heads"], arch["kv_rank"]
    dn, dr, dv = arch["nope"], arch["rope"], arch["v_dim"]
    q = (x @ lp["wq"]).reshape(B, S, N, dn + dr)
    q_pe = _rope(q[..., dn:], positions, arch["theta"], arch["interleave"])
    kv_a = x @ lp["wkv_a"]                                   # [B, S, r+dr]
    c_kv = _rms_norm(kv_a[..., :r], lp["kv_a_norm"], arch["eps"])
    k_pe = _rope(kv_a[..., r:][:, :, None, :], positions, arch["theta"],
                 arch["interleave"])                          # [B, S, 1, dr]
    kv = (c_kv @ lp["wkv_b"]).reshape(B, S, N, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, N, dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    out = _attention(q, k, kv[..., dn:])                      # [B,S,N,dv]
    return out.reshape(B, S, N * dv) @ lp["wo"]


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _route(x, lp, arch):
    """[T, H] -> routing weight of every token for every expert [T, E],
    zero outside its top-k (``DeepseekV3TopkRouter`` + the scaling in
    ``DeepseekV3MoE``): scores are sigmoids of the router logits; the
    top-k is taken on score + correction bias; the weights are the scores
    WITHOUT the bias, normalised over the chosen k, times the scaling
    factor."""
    logits = x @ lp["gate_w"]
    scores = jax.nn.sigmoid(logits) if arch["sigmoid"] \
        else jax.nn.softmax(logits, axis=-1)
    choose = scores + lp["gate_bias"] if "gate_bias" in lp else scores
    _, idx = jax.lax.top_k(choose, arch["top_k"])             # [T, k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if arch["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * arch["route_scale"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot)


def _moe(x, lp, lp_experts, arch):
    """lp: the layer's small leaves in float32; lp_experts: its routed
    experts as passed (upcast one expert at a time)."""
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    weight = _route(xt, lp, arch)                             # [T, E]

    def one_expert(y, ew):
        w_gate, w_up, w_down, we = ew
        out = _mlp(xt, w_gate.astype(jnp.float32), w_up.astype(jnp.float32),
                   w_down.astype(jnp.float32))
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xt),
                        (lp_experts["w_gate"], lp_experts["w_up"],
                         lp_experts["w_down"], weight.T))
    if "sw_up" in lp:
        y = y + _mlp(xt, lp["sw_gate"], lp["sw_up"], lp["sw_down"])
    return y.reshape(B, S, H)


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(x, lp, positions, arch, experts: bool):
    lp_experts = {k: lp[k] for k in _EXPERT_LEAVES} if experts else None
    lp = _f32({k: v for k, v in lp.items()
               if not (experts and k in _EXPERT_LEAVES)})
    x = x + _mla(_rms_norm(x, lp["ln1"]["scale"], arch["eps"]), lp,
                 positions, arch)
    h = _rms_norm(x, lp["ln2"]["scale"], arch["eps"])
    if experts:
        return x + _moe(h, lp, lp_experts, arch)
    return x + _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"])


_layer_jit = jax.jit(_layer, static_argnames=("arch", "experts"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, x @ w.astype(jnp.float32), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def forward_logits(params, tokens, arch: Dict[str, Any], at=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with ``at`` (a list
    of positions) the logits of those positions alone, [B, len(at), V]: a
    prompt of several thousand tokens is 0.66 MB of logits a position."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
        for key, experts in (("dense_blocks", False), ("blocks", True)):
            if key not in params:
                continue
            depth = jax.tree.leaves(params[key])[0].shape[0]
            for layer in range(depth):
                lp = jax.tree.map(lambda a: a[layer], params[key])
                x = _layer_jit(x, lp, positions, arch=arch, experts=experts)
        if at is not None:
            x = x[:, jnp.asarray(at)]
            S = x.shape[1]
        x = _rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                     jnp.float32), arch["eps"])
        head = params["lm_head"] if "lm_head" in params \
            else jnp.asarray(params["tok_emb"]).T
        V = head.shape[1]
        out = jnp.zeros((B, S, V), jnp.float32)
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
