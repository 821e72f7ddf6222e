"""Plain reference: the ``mellum`` family (JetBrains Mellum 2: the Qwen3-MoE
block over sliding-window and full attention layers, rotary by layer type),
forward pass, next-token loss with its auxiliary term and, through
``jax.grad`` of :func:`loss`, gradients; straightforward ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``.

Written from the published descriptions (the source's ``config.json``;
Hugging Face ``modeling_qwen3_moe.py`` for the block its keys name;
``modeling_rope_utils.py::_compute_yarn_parameters`` for the full layers'
rotary), not from the program: it imports nothing of ``deepspeed_tpu``. It
reads the *layout* of the program's parameter tree (``blocks`` stacked by
layer: ``ln1 ln2`` as ``{"scale"}``, ``wq wk wv wo`` stored ``[in, out]``,
``q_norm k_norm [D]``, ``gate_w [H, router experts]``, ``w_gate w_up [held,
H, F]``, ``w_down [held, F, H]``; ``tok_emb``, ``final_norm``, ``lm_head``)
because the weights under test are the program's own.

One layer, for a layer type in {sliding_attention, full_attention}::

    u  = RMSNorm(x; ln1)
    q, k, v = u Wq, u Wk, u Wv                  -> heads of D
    q, k = RMSNorm_head(q; q_norm), RMSNorm_head(k; k_norm)
    q, k = rope_type(q), rope_type(k)           cos / sin of the TYPE's
                                                inv_freq, times its factor
    a[t] = softmax_s(q[t].k[s] / sqrt(D)) v[s]  over s <= t, and
                                                s > t - window if sliding
    x  = x + a Wo
    u2 = RMSNorm(x; ln2)
    p  = softmax(u2 Wr) in float32 over ALL the router's experts
    E(t) = the top-k of p;  w_e = p_e / sum_{e' in E(t)} p_e'
    x  = x + sum_{e in E(t), e HELD} w_e W2_e(silu(u2 Wg_e) * (u2 W1_e))

No kernel, no sort, no grouped matmul: an explicit mask a layer type,
attention in blocks of queries, plain ``top_k`` over the router's whole
width and a Python loop over the experts HELD, each over every row under a
0/1 mask. A SHARE of the experts (``router_experts`` / ``first_expert`` in
the configuration): the sum above runs over the held experts alone, which
is this chip's part of the layer; the other shares' parts are other
chips'.

The loss: mean next-token cross-entropy + ``aux_coef`` x the SUM over the
layers of ``E * sum_e P_e f_e``, ``P_e`` the mean probability of expert e
over the batch's rows and ``f_e`` the share of rows whose FIRST choice is e
(the Switch / GShard balance term, over the router's whole width). The
source's config has no key for it: the term and its coefficient are under
``assumed`` in the configuration file.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rope_inv_freq(dim: int, section: Dict[str, Any]):
    """(inverse frequencies [dim / 2], the factor that multiplies cos and
    sin) of one ``rope_parameters`` section: ``default``, or ``yarn`` as
    published (arXiv:2309.00071 and ``_compute_yarn_parameters``)."""
    base = float(section["rope_theta"])
    pos = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if section.get("rope_type", "default") == "default":
        return 1.0 / pos, 1.0
    assert section["rope_type"] == "yarn", section
    factor = float(section["factor"])
    orig = float(section["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(section["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(section["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (factor * pos)) * (1 - extrapolated) \
        + (1.0 / pos) * extrapolated
    att = section.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(att)


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source's keys as run
    (``hf``) and the configuration file's ``assumed``."""
    assumed = config.get("assumed", {})
    held = hf["num_experts"]
    return dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        eps=hf["rms_norm_eps"], window=int(hf["sliding_window"]),
        layer_types=tuple(hf["layer_types"]),
        rope={k: dict(v) for k, v in hf["rope_parameters"].items()},
        top_k=hf["num_experts_per_tok"],
        norm_topk=bool(hf["norm_topk_prob"]),
        router_experts=int(hf.get("router_experts", held)),
        first_expert=int(hf.get("first_expert", 0)), held=held,
        qk_norm=bool(assumed.get("qk_norm", True)),
        aux_coef=float(assumed.get("router_aux_loss_coef", 0.001)),
        faults=())


def _lin(x, w, faults=()):
    """A linear layer, ``x @ w``. Under the fault ``float8`` both operands
    are rounded to float8_e4m3 first: the reference COMPUTED in the nearest
    precision below the configuration's, which the cell's limit has to
    fail (softmax probabilities and norms are never rounded: e4m3 has no
    value under 2^-9)."""
    if "float8" in faults:
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        w = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, section):
    """x [B, S, N, D] at positions 0 .. S-1 (half-split pairs)."""
    inv, att = _rope_inv_freq(x.shape[-1], section)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * (jnp.cos(emb) * att) + _rotate_half(x) * (jnp.sin(emb) * att)


def _attention(q, k, v, window: int):
    """Causal softmax attention under an explicit mask, ``window`` 0: every
    earlier position. q [B, S, N, D]; k, v [B, S, K, D]."""
    B, S, N, D = q.shape
    rep = N // k.shape[2]
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


def _experts(u, lp, arch):
    """(this share's part of the routed result [T, H], the balance term of
    the layer) for normed rows ``u [T, H]``."""
    E, faults = arch["router_experts"], arch["faults"]
    p = jax.nn.softmax(_lin(u, lp["gate_w"], faults), axis=-1)   # [T, E]
    top_p, top_e = jax.lax.top_k(p, arch["top_k"])
    if arch["norm_topk"] and "no-renorm" not in arch["faults"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    first = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32)
    balance = E * jnp.sum(jnp.mean(p, axis=0) * jnp.mean(first, axis=0))
    lo = arch["first_expert"] + ("share-off-by-one" in arch["faults"])
    y = jnp.zeros_like(u)
    for j in range(arch["held"]):
        # the weight of held expert j for each row: its normalised
        # probability where the row chose it, else nothing
        w = jnp.sum(jnp.where(top_e == lo + j, top_p, 0.0), axis=-1)
        out = _lin(jax.nn.silu(_lin(u, lp["w_gate"][j], faults))
                   * _lin(u, lp["w_up"][j], faults), lp["w_down"][j], faults)
        y = y + w[:, None] * out
    return y, balance


def _layer(x, lp, layer_type: str, arch):
    lp = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), lp)
    B, S, H = x.shape
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    faults = arch["faults"]
    u = _rms_norm(x, lp["ln1"]["scale"], arch["eps"])
    q = _lin(u, lp["wq"], faults).reshape(B, S, N, D)
    k = _lin(u, lp["wk"], faults).reshape(B, S, K, D)
    v = _lin(u, lp["wv"], faults).reshape(B, S, K, D)
    if arch["qk_norm"]:
        q = _rms_norm(q, lp["q_norm"], arch["eps"])
        k = _rms_norm(k, lp["k_norm"], arch["eps"])
    section = arch["rope"][layer_type]
    if layer_type == "full_attention" and "no-yarn" in faults:
        section = arch["rope"]["sliding_attention"]
    q, k = _rope(q, section), _rope(k, section)
    window = arch["window"] if layer_type == "sliding_attention" \
        and "no-window" not in faults else 0
    x = x + _lin(_attention(q, k, v, window).reshape(B, S, N * D),
                 lp["wo"], faults)
    u2 = _rms_norm(x, lp["ln2"]["scale"], arch["eps"])
    y, balance = _experts(u2.reshape(B * S, H), lp, arch)
    return x + y.reshape(B, S, H), balance


class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))


_layer_jit = jax.jit(_layer, static_argnames=("layer_type", "arch"))


def forward_hidden(params, tokens, arch: Dict[str, Any], jit: bool = True):
    """tokens [B, S] int32 -> (the last layer's output under the final norm
    [B, S, H] float32, the sum over layers of the balance term)."""
    arch = _Frozen(arch)
    layer = _layer_jit if jit else _layer
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
        balance = jnp.float32(0.0)
        for i, layer_type in enumerate(arch["layer_types"]):
            lp = jax.tree.map(lambda a: a[i], params["blocks"])
            x, b = layer(x, lp, layer_type=layer_type, arch=arch)
            balance = balance + b
        x = _rms_norm(x, jnp.asarray(params["final_norm"]["scale"],
                                     jnp.float32), arch["eps"])
        return x, balance


def forward_logits(params, tokens, arch: Dict[str, Any]):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    x, _ = forward_hidden(params, tokens, arch)
    with jax.default_matmul_precision("highest"):
        return _lin(x, jnp.asarray(params["lm_head"], jnp.float32),
                    arch["faults"])


def loss(params, tokens, arch: Dict[str, Any], jit: bool = False):
    """The scalar a step minimises: mean next-token cross-entropy over
    every position of every sequence + ``aux_coef`` x the balance terms.
    Traceable: ``jax.grad`` of it gives the reference's gradients."""
    tokens = jnp.asarray(tokens)
    x, balance = forward_hidden(params, tokens, arch, jit=jit)
    head = jnp.asarray(params["lm_head"], jnp.float32)
    total = jnp.float32(0.0)
    with jax.default_matmul_precision("highest"):
        for row in range(tokens.shape[0]):          # a sequence's logits
            logp = jax.nn.log_softmax(
                _lin(x[row, :-1], head, arch["faults"]), axis=-1)
            total = total - jnp.take_along_axis(
                logp, tokens[row, 1:, None], axis=1).sum()
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    return total / count + arch["aux_coef"] * balance


def next_token_loss(params, tokens, arch: Dict[str, Any]) -> float:
    return float(loss(params, tokens, arch, jit=True))
