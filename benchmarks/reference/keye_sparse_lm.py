"""Plain reference: the language model of the ``KeyeVL2`` family
(Kwai Keye-VL-2.0-30B-A3B), forward pass and next-token loss, in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.

Written from the equations of ISSUE 46 (the catalog's row: "GQA 32Q/4KV
with DeepSeek-Sparse-Attention indexer (sa_config topk 2048)" on the
Qwen3-MoE block these widths are), not from the program: it imports
nothing of ``deepspeed_tpu``. Every layer, with ``u = RMSNorm(x)``:

* ``q = u Wq [S, N, D]``, ``k = u Wk``, ``v = u Wv [S, K, D]``; ``q`` and
  ``k`` RMS-normed per head with a learned gain, then rotated (the whole
  head, half-split pairs, unscaled: for token ids the three position
  streams of ``mrope_section`` carry one index);
* the indexer: ``qI = u WqI [S, Hi, Di]``, ``kI = LayerNorm(u WkI) [S,
  Di]`` (gain and bias), ``w = u Ww [S, Hi]``; ``qI`` and ``kI`` rotated
  over their ``Di`` columns at the layer's theta;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
* ``S_t``: the ``topk`` positions ``s <= t`` of the largest ``I[t, s]``
  (``lax.top_k`` a row: the lower position first among equals); every
  ``s <= t`` while ``t + 1 <= topk``;
* ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)] /
  sqrt(D)) v[s, g(h)]``; ``x += concat(o) Wo``;
* ``u2 = RMSNorm(x)``; ``p = softmax(u2 Wr)`` over all the router's
  experts; top-k; weights ``p / sum of the chosen p`` (``norm_topk_prob``);
  ``x += sum_e weight_e Wdown_e(silu(u2 Wgate_e) * (u2 Wup_e))``;

then the final RMSNorm and the head.

It reads the *layout* of the program's parameter tree (``blocks``, leaves
stacked by layer: ``ln1 ln2``; ``wq wk wv wo q_norm k_norm``; ``idx_wq
idx_wk idx_ww idx_k_norm``; ``gate_w``; ``w_gate w_up w_down`` with a
leading expert axis; matrices ``[in, out]``) because the weights under test
are the program's. No kernel, no cache, no block table, no threshold: the
scores written out, ``lax.top_k`` a row over the causal prefix, a mask, a
plain top-k over the router's experts and a loop over the experts HELD.

A SHARE of the expert layers (the ``model-configs`` guide, section 4) is
given as the program is given it: the router is as wide as the published
count of experts and chooses among all of them; the loop runs over the
experts the parameter tree holds (``num_experts`` from ``first_expert``);
the logits are over the rows of the head that are held.

Departures, each deliberate: queries are met a block at a time (``Q_BLOCK``
rows against every key: 7k positions fit beside a serving engine); the
head is applied a slice of the vocabulary at a time into one buffer;
weights are upcast to float32 a layer (an expert) at a time.

``arch["faults"]`` names equations to get WRONG, for the probe and the
tests that make a mistake on purpose (``FAULTS``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 64
VOCAB_BLOCK = 8192

#: the mistakes ``arch["faults"]`` may name
FAULTS = (
    "dense",                 # the choice ignored: every s <= t
    "half-topk",             # the topk / 2 largest
    "no-relu",               # the indexer's scores without their relu
    "index-key-before",      # position s scored by the index key of s - 1
    "next-layers-indexer",   # a layer chooses by the next layer's indexer
    "no-index-key-norm",     # no LayerNorm on the index key
    "next-experts",          # the held matrices under the next share's weights
)

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
_INDEX_LEAVES = ("idx_wq", "idx_wk", "idx_ww", "idx_k_norm")


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "KeyeVL2":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    scaling = hf.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default" \
            or hf.get("decoder_sparse_step", 1) != 1 \
            or hf.get("mlp_only_layers") or hf.get("use_sliding_window"):
        raise ValueError("reference: unscaled rotary, experts in every "
                         "layer and no window are what is written")
    sa = hf["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("reference: the indexer has one key head")
    return dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        topk=int(sa["topk"]), eps=hf["rms_norm_eps"],
        theta=float(hf["rope_theta"]), top_k=hf["num_experts_per_tok"],
        route_norm=bool(hf.get("norm_topk_prob", True)),
        first_expert=int(hf.get("first_expert", 0)), faults=())


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every product with a weight matrix (the probe's float8 reading
    edits this one line)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


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


def _attention(q, k, v, qi, ki, w, arch, given):
    """q [S, K, rep, D]; k, v [S, K, D]; the indexer's qi [S, Hi, Di], ki
    [S, Di], w [S, Hi]: a block of queries at a time, the scores of every
    earlier position written out, the ``topk`` largest a row chosen, the
    softmax over the chosen under an explicit mask. ``given [S, S]`` bool:
    the sets to use instead of choosing (None: choose). Returns (the
    attended values [S, K * rep * D], the sets chosen [S, S] bool)."""
    S, K, rep, D = q.shape
    faults = arch["faults"]
    topk = arch["topk"] // 2 if "half-topk" in faults else arch["topk"]
    pad = -S % Q_BLOCK
    blocks = lambda x: jnp.pad(                                  # noqa: E731
        x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (-1, Q_BLOCK) + x.shape[1:])
    kpos = jnp.arange(S)

    def block(args):
        qb, qib, wb, lo, givenb = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)     # pad rows
        causal = kpos[None, :] <= qpos[:, None]                 # [Q, S]
        if given is not None:
            chosen = givenb & causal
        elif "dense" in faults or S <= topk:
            chosen = causal
        else:
            dots = jnp.einsum("qjd,sd->qjs", qib, ki)
            if "no-relu" not in faults:
                dots = jax.nn.relu(dots)
            score = jnp.einsum("qjs,qj->qs", dots, wb)
            _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
            chosen = jnp.zeros((Q_BLOCK, S), jnp.bool_).at[
                jnp.arange(Q_BLOCK)[:, None], idx].set(True) & causal
        s = jnp.einsum("qkrd,skd->krqs", qb, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("krqs,skd->qkrd", p, v), chosen

    givenb = blocks(given) if given is not None \
        else jnp.zeros((blocks(w).shape[0], Q_BLOCK, 1), jnp.bool_)
    out, chosen = jax.lax.map(block, (
        blocks(q), blocks(qi), blocks(w),
        jnp.arange(0, S + pad, Q_BLOCK), givenb))
    return (out.reshape(S + pad, K * rep * D)[:S],
            chosen.reshape(S + pad, S)[:S])


def _attn(u, lp, ip, arch, given):
    """``lp``: the layer's leaves; ``ip``: the leaves of the indexer it
    chooses by (its own, but for a fault)."""
    S = u.shape[0]
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    Hi, Di = arch["index_heads"], arch["index_dim"]
    theta = arch["theta"]
    q = _rms_norm(_linear(u, lp["wq"]).reshape(S, N, D), lp["q_norm"], arch["eps"])
    k = _rms_norm(_linear(u, lp["wk"]).reshape(S, K, D), lp["k_norm"], arch["eps"])
    v = _linear(u, lp["wv"]).reshape(S, K, D)
    q, k = _rope(q, theta), _rope(k, theta)
    qi = _rope(_linear(u, ip["idx_wq"]).reshape(S, Hi, Di), theta)
    ki = _linear(u, ip["idx_wk"])
    if "no-index-key-norm" not in arch["faults"]:
        ki = _layer_norm(ki, ip["idx_k_norm"], arch["eps"])
    ki = _rope(ki[:, None, :], theta)[:, 0]
    if "index-key-before" in arch["faults"]:
        ki = jnp.concatenate([ki[:1], ki[:-1]])
    o, chosen = _attention(q.reshape(S, K, N // K, D), k, v, qi, ki,
                           _linear(u, ip["idx_ww"]), arch, given)
    return _linear(o, lp["wo"]), chosen


def _mlp(x, w_gate, w_up, w_down):
    return _linear(jax.nn.silu(_linear(x, w_gate)) * _linear(x, w_up), w_down)


def _route(u, lp, arch):
    """[T, H] -> the routing weight of every token for every expert of the
    ROUTER [T, E], zero outside its top-k."""
    p = jax.nn.softmax(_linear(u, lp["gate_w"]), axis=-1)
    w, idx = jax.lax.top_k(p, arch["top_k"])
    if arch["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, p.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot)


def _moe(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: the routed experts
    HELD, every layer's ``[layers, held, in, out]`` as passed, of which
    ``layer`` is this one's (one expert's matrices are read and upcast at
    a time)."""
    weight = _route(u, lp, arch)                               # [T, E]
    held = stack["w_up"].shape[1]
    first = arch["first_expert"]
    if "next-experts" in arch["faults"]:
        first = first + held
    weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=1)

    def one_expert(e, y):
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return y + we * _mlp(u, w_gate, w_up, w_down)

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(u))


def _layer(x, lp, ip, stack, layer, arch, given):
    """x [S, H] of one sequence. Returns (x, the sets chosen [S, S])."""
    lp, ip = _f32(lp), _f32(ip)
    eps = arch["eps"]
    a, chosen = _attn(_rms_norm(x, lp["ln1"]["scale"], eps), lp, ip, arch,
                      given)
    x = x + a
    u = _rms_norm(x, lp["ln2"]["scale"], eps)
    return x + _moe(u, lp, stack, layer, arch), chosen


_layer_jit = jax.jit(_layer, static_argnames=("arch",))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, x @ w.astype(jnp.float32), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple))
                                  else v) for k, v in self.items())))


def forward_logits(params, tokens, arch: Dict[str, Any], at=None,
                   chosen=None, given=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32 over the rows of
    the head that ``params`` holds; with ``at`` (a list of positions) the
    logits of those positions alone, [B, len(at), V]. ``chosen``: a list
    that receives, for every sequence and layer in turn, the sets the
    layer's rows attended to, [S, S] bool; ``given``: such a list to use
    instead of choosing (a toy size's: a set a row of every layer)."""
    arch = _Frozen(arch, faults=tuple(arch.get("faults", ())))
    assert set(arch["faults"]) <= set(FAULTS), arch["faults"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        emb = jnp.asarray(params["tok_emb"])
        blocks = params["blocks"]
        depth = blocks["ln1"]["scale"].shape[0]
        stack = {k: blocks[k] for k in _EXPERT_LEAVES}
        small = {k: v for k, v in blocks.items() if k not in _EXPERT_LEAVES}
        rows, n = [], 0
        for b in range(tokens.shape[0]):
            x = emb[tokens[b]].astype(jnp.float32)
            for layer in range(depth):
                lp = jax.tree.map(lambda a: a[layer], small)
                other = (layer + 1) % depth \
                    if "next-layers-indexer" in arch["faults"] else layer
                ip = jax.tree.map(lambda a: a[other],
                                  {k: small[k] for k in _INDEX_LEAVES})
                x, sets = _layer_jit(
                    x, lp, ip, stack, layer, arch=arch,
                    given=None if given is None else given[n])
                if chosen is not None:
                    chosen.append(sets)
                n += 1
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
