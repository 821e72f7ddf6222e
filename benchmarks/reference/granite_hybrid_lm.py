"""Plain reference: the ``granitemoehybrid`` family's decoder (Granite 4.0-H:
a stack of PAIRED blocks, a Mamba-2 mixer or grouped-query attention AND
routed experts beside a shared gated MLP in every layer, under four scalars
of a maximal-update parametrisation), forward pass and next-token loss, in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.

Written from the family's published description as ISSUE 60 sets it out
(the Mamba-2 paper's recurrence and the family's modelling code), not from
the program: it imports nothing of ``deepspeed_tpu``. The equations (``x``
the residual stream, no bias but the convolution's, no positions anywhere:
``position_embedding_type`` ``nope``):

* ``x = embedding_multiplier * Emb[token]``; every layer: ``x += r *
  Mixer(N_in x)``, then ``u = N_post x``, ``x += r * (Experts(u) +
  Shared(u))``, ``r`` the ``residual_multiplier``, RMSNorm with eps
  ``rms_norm_eps``; ``layer_types`` gives the mixer a layer, ``mamba`` or
  ``attention``; after the last layer one RMSNorm, then the TIED head,
  ``logits = N_f(x) Emb^T / logits_scaling``;
* ``mamba``, Mamba-2, on the normed ``u [S, H]``: ``[z | xBC | dt] = u
  W_in`` (inner | inner + 2 G N | heads); ``xBC = silu(conv(xBC) + b)``,
  depthwise and causal over ``mamba_d_conv`` taps (the last tap on the row
  itself, zeros before the sequence's start); ``[x | B | C] = xBC``, ``x``
  as ``[heads, P]``, ``B`` and ``C`` as ``[G, N]``, head ``h`` reads group
  ``h // (heads / G)`` (published: ONE group, every head the same ``B`` and
  ``C``); ``delta_h = softplus(dt_h + dt_bias_h)`` (no clamp); ``a_h =
  exp(-exp(A_log_h) delta_h)``; from a zero state ``S_h [P, N]``, one row
  after another: ``S_h = a_h S_h + delta_h x_h B_g^T``; ``y_h = S_h C_g +
  D_h x_h``; ``y = gain * rmsnorm_by_group(y * silu(z))``, the mean square
  over each of the G groups of ``inner / G`` channels (published: over all
  8,192), the gate BEFORE the norm; then ``W_out``;
* ``attention``: query heads and key-value heads of ``hidden / heads``,
  causal softmax of ``q k^T * attention_multiplier``, NO rotary; ``W_o``;
* experts: ``l = u W_r`` over ALL the model's experts in float32; the
  ``num_experts_per_tok`` largest are chosen; weights = softmax over the
  CHOSEN logits; expert ``e``: ``W_out_e (silu(W_gate_e u) * (W_up_e u))``;
  of the chosen only those HELD here (``num_local_experts`` from
  ``first_expert``) are added; plus the shared MLP's ``W_out_s
  (silu(W_gate_s u) * (W_up_s u))`` on the row itself, without a gate.

It reads the *layout* of the program's parameter tree (``tok_emb``;
``blocks``: ``ln1``, ``ln2``, ``gate_w``, ``w_gate`` / ``w_up`` / ``w_down``
``[layers, held, in, out]``, ``sw_gate`` / ``sw_up`` / ``sw_down`` stacked
over all layers; ``mamba2: {w_in conv_w [taps, C] conv_b dt_bias a_log
skip_scale gate_norm wo}`` over the ``mamba`` layers; ``attn: {wq wk wv
wo}`` over the ``attention`` layers; matrices ``[in, out]``) because the
weights under test are the program's. No kernel, no cache, no chunks, no
sort or grouped matmul.

``arch["faults"]`` (empty but in the probes and tests that make a mistake
on purpose) names equations to get wrong: :data:`FAULTS`.

Departures, each deliberate: queries are met a block at a time, the head a
slice of the vocabulary at a time, weights upcast a layer (an expert) at a
time, a mixer's in-projection a column block at a time, and a layer waits
for the layer before it: so the check fits beside a serving engine.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 64
VOCAB_BLOCK = 8192

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
_KINDS = {"mamba": "mamba2", "attention": "attn"}
FAULTS = ("residual-multiplier-one", "embedding-multiplier-one",
          "scores-by-head-dim", "logits-undivided", "shared-dropped",
          "gate-dropped", "norm-by-8-groups", "rotary-on-attention",
          "top-k-less-one", "decay-dropped")


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "granitemoehybrid":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    kinds = tuple(hf["layer_types"])
    assert len(kinds) == hf["num_hidden_layers"] \
        and set(kinds) <= set(_KINDS), "`mamba` or `attention` a layer"
    if hf.get("position_embedding_type", "nope") != "nope" \
            or hf.get("hidden_act", "silu") != "silu" \
            or not hf.get("tie_word_embeddings", True):
        raise ValueError("reference: no positions, SiLU-gated experts and a "
                         "tied head are what is written")
    return dict(
        kinds=tuple(_KINDS[k] for k in kinds),
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        m_heads=hf["mamba_n_heads"], m_dim=hf["mamba_d_head"],
        groups=hf["mamba_n_groups"], state=hf["mamba_d_state"],
        eps=hf["rms_norm_eps"], top_k=hf["num_experts_per_tok"],
        first_expert=int(hf.get("first_expert", 0)),
        emb_mult=float(hf["embedding_multiplier"]),
        resid_mult=float(hf["residual_multiplier"]),
        attn_mult=float(hf["attention_multiplier"]),
        logits_div=float(hf["logits_scaling"]),
        theta=float(hf.get("rope_theta", 10000.0)), faults=frozenset())


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every linear layer of the model: projections, experts, the router,
    the head (one place, so that a probe can read the whole reference in a
    lower precision: ``tools/granite_hybrid_probe.py``)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _short_conv(x, taps):
    """x [S, C] through a depthwise causal convolution, taps [n, C]: explicit
    shifts by 1 .. n-1 rows, zeros before the sequence's start; tap n-1
    meets the row itself."""
    n = taps.shape[0]
    out = taps[n - 1] * x
    for back in range(1, n):
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype),
                                   x[:-back]])[:x.shape[0]]
        out = out + taps[n - 1 - back] * shifted
    return out


def _mamba2(u, lp, arch):
    S = u.shape[0]
    nh, P, G, N = arch["m_heads"], arch["m_dim"], arch["groups"], \
        arch["state"]
    di, faults = nh * P, arch["faults"]
    # ``W_in``'s three column blocks one product each (``z`` only when the
    # gate needs it: an ``[S, 16768]`` product is never whole in memory)
    w_z, w_xbc, w_dt = (lp["w_in"][:, :di],
                        lp["w_in"][:, di:2 * di + 2 * G * N],
                        lp["w_in"][:, 2 * di + 2 * G * N:])
    xbc, dt = _linear(u, w_xbc), _linear(u, w_dt)
    xbc = jax.nn.silu(_short_conv(xbc, lp["conv_w"]) + lp["conv_b"])
    x = xbc[:, :di].reshape(S, nh, P)
    group = jnp.arange(nh) // (nh // G)
    B = xbc[:, di:di + G * N].reshape(S, G, N)
    C = xbc[:, di + G * N:].reshape(S, G, N)
    delta = jax.nn.softplus(dt + lp["dt_bias"])                 # [S, nh]
    a = jnp.exp(-jnp.exp(lp["a_log"]) * delta)
    if "decay-dropped" in faults:
        a = jnp.ones_like(a)

    def row(state, xs):
        x_t, b_t, c_t, a_t, d_t = xs
        state = a_t[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group])

    _, y = jax.lax.scan(row, jnp.zeros((nh, P, N), jnp.float32),
                        (x, B, C, a, delta))
    y = y + lp["skip_scale"][None, :, None] * x
    y = y.reshape(S, di) * jax.nn.silu(_linear(u, w_z))
    # the gated norm's groups: the model's own (published: one, the whole)
    y = y.reshape(S, 8 if "norm-by-8-groups" in faults else G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + arch["eps"])
    return _linear(y.reshape(S, di) * lp["gate_norm"], lp["wo"])


def _rope(x, theta):
    """A fault made on purpose (``rotary-on-attention``): x [S, n, d]
    rotated at positions 0 .. S-1, pairs split by halves."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def _attention(u, lp, arch):
    """Causal softmax attention, a block of queries at a time against
    every key under an explicit mask; a key-value head repeated for the
    query heads that share it."""
    S = u.shape[0]
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    faults = arch["faults"]
    factor = D ** -0.5 if "scores-by-head-dim" in faults \
        else arch["attn_mult"]
    q = _linear(u, lp["wq"]).reshape(S, N, D)
    k = _linear(u, lp["wk"]).reshape(S, K, D)
    v = _linear(u, lp["wv"]).reshape(S, K, D)
    if "rotary-on-attention" in faults:
        q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
    k, v = jnp.repeat(k, N // K, axis=1), jnp.repeat(v, N // K, axis=1)
    pad = -S % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(args):
        qb, lo = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)  # pad rows
        seen = kpos[None, :] <= qpos[:, None]
        s = jnp.einsum("qnd,snd->nqs", qb, k) * factor
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nqs,snd->qnd", p, v)

    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, N, D),
                              jnp.arange(0, S + pad, Q_BLOCK)))
    return _linear(out.reshape(S + pad, -1)[:S], lp["wo"])


def _gated(x, w_gate, w_up, w_down, arch):
    """A gated MLP: ``W_down (silu(W_gate x) * (W_up x))``."""
    g = jax.nn.silu(_linear(x, w_gate))
    if "gate-dropped" not in arch["faults"]:
        g = g * _linear(x, w_up)
    return _linear(g, w_down)


def _route(u, lp, arch):
    """[T, H] -> (routing weight of every token for every expert of the
    MODEL [T, E], zero outside its top-k; the experts chosen [T, k])."""
    logits = _linear(u, lp["gate_w"])
    top, idx = jax.lax.top_k(logits, arch["top_k"] - (
        "top-k-less-one" in arch["faults"]))
    w = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot), idx


def _experts(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: every layer's
    experts ``[layers, experts held, in, out]`` as passed, of which
    ``layer`` is this one's and expert ``e`` the model's ``first_expert +
    e`` (one expert's matrices are read and upcast at a time)."""
    weight, chosen = _route(u, lp, arch)                      # [T, E]

    def one_expert(e, r):
        w_gate, w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(
            weight, arch["first_expert"] + e, 1, axis=1)
        return r + we * _gated(u, w_gate, w_up, w_down, arch)

    r = jax.lax.fori_loop(0, stack["w_up"].shape[1], one_expert,
                          jnp.zeros_like(u))
    if "shared-dropped" not in arch["faults"]:
        r = r + _gated(u, lp["sw_gate"], lp["sw_up"], lp["sw_down"], arch)
    return r, chosen


def _layer(x, lp, stack, layer, arch, kind: str):
    """x [S, H] of one sequence; ``lp``: the layer's two norms, its mixer's
    leaves and its experts' small leaves, flat; ``stack``: the experts'
    matrices (``layer`` this layer's index among them). Returns (x, the
    experts every position chose [S, k])."""
    lp = _f32(lp)
    r = 1.0 if "residual-multiplier-one" in arch["faults"] \
        else arch["resid_mult"]
    u = _rms_norm(x, lp["ln1"]["scale"], arch["eps"])
    x = x + r * (_mamba2 if kind == "mamba2" else _attention)(u, lp, arch)
    u = _rms_norm(x, lp["ln2"]["scale"], arch["eps"])
    f, chosen = _experts(u, lp, stack, layer, arch)
    return x + r * f, chosen


_layer_jit = jax.jit(_layer, static_argnames=("arch", "kind"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo, div):
    return jax.lax.dynamic_update_slice_in_dim(
        out, _linear(x, w.astype(jnp.float32).T) / div, lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def forward_logits(params, tokens, arch: Dict[str, Any], at=None,
                   routes=None):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with ``at`` (a list
    of positions) the logits of those positions alone, [B, len(at), V].
    ``routes``: a list that receives, for every sequence and layer in turn,
    the experts each (``at``) position chose, [positions, k]."""
    arch = _Frozen(arch)
    faults = arch["faults"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        emb = jnp.asarray(params["tok_emb"])
        blocks = params["blocks"]
        assert blocks["ln1"]["scale"].shape[0] == len(arch["kinds"]), \
            "depth vs the layer types"
        stack = {k: blocks[k] for k in _EXPERT_LEAVES}
        flat = {k: v for k, v in blocks.items()
                if k not in _EXPERT_LEAVES and k not in _KINDS.values()}
        rows = []
        for b in range(tokens.shape[0]):
            x = emb[tokens[b]].astype(jnp.float32)
            if "embedding-multiplier-one" not in faults:
                x = x * arch["emb_mult"]
            seen = dict.fromkeys(_KINDS.values(), 0)
            for layer, kind in enumerate(arch["kinds"]):
                nth = seen[kind]
                seen[kind] += 1
                lp = jax.tree.map(lambda a: a[layer], flat)
                lp.update(jax.tree.map(lambda a: a[nth], blocks[kind]))
                # (a layer at a time on the device too: programs queued
                # ahead each hold their temporaries beside the engine's)
                x, chosen = jax.block_until_ready(_layer_jit(
                    x, lp, stack, layer, arch=arch, kind=kind))
                if routes is not None:
                    routes.append(chosen if at is None
                                  else chosen[jnp.asarray(at)])
            rows.append(x if at is None else x[jnp.asarray(at)])
        x = _rms_norm(jnp.stack(rows), jnp.asarray(
            params["final_norm"]["scale"], jnp.float32), arch["eps"])
        div = 1.0 if "logits-undivided" in faults else arch["logits_div"]
        V = emb.shape[0]
        out = jnp.zeros(x.shape[:2] + (V,), jnp.float32)
        for lo in range(0, V, VOCAB_BLOCK):
            out = _head_slice(out, x, emb[lo:lo + VOCAB_BLOCK], lo, div)
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
