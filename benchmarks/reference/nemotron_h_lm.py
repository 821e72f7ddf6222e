"""Plain reference: the ``nemotron_h`` family's decoder (Nemotron-3: a stack
whose every layer is ONE norm and ONE sublayer, a Mamba-2 mixer, grouped-query
attention without rotary, or an expert layer whose routed experts work in
a latent of the row), forward pass and next-token loss, in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.

Written from the family's published description as ISSUE 53 sets it out
(the Mamba-2 paper's recurrence and the family's modelling code), not from
the program: it imports nothing of ``deepspeed_tpu``. The equations (``x``
the residual stream, no bias but the convolution's, no positions anywhere):

* every layer: ``x += f(N x)``, RMSNorm with eps ``layer_norm_epsilon``;
  ``hybrid_override_pattern`` gives ``f`` a layer: ``M``, ``*`` or ``E``;
  after the last layer one RMSNorm, then the untied head;
* ``M``, Mamba-2, on the normed ``u [S, H]``: ``[z | xBC | dt] = u W_in``
  (inner | inner + 2 G N | heads); ``xBC = silu(conv(xBC) + b)``, depthwise
  and causal over ``conv_kernel`` taps (the last tap on the row itself,
  zeros before the sequence's start); ``[x | B | C] = xBC``, ``x`` as
  ``[heads, P]``, ``B`` and ``C`` as ``[G, N]``, head ``h`` reads group ``h
  // (heads / G)``; ``delta_h = softplus(dt_h + dt_bias_h)`` (no clamp);
  ``a_h = exp(-exp(A_log_h) delta_h)``; from a zero state ``S_h [P, N]``,
  one row after another: ``S_h = a_h S_h + delta_h x_h B_g^T``; ``y_h = S_h
  C_g + D_h x_h``; ``y = gain * rmsnorm_by_group(y * silu(z))``, the mean
  square over each of the G groups of ``inner / G`` channels, the gate
  BEFORE the norm; then ``W_out``;
* ``*``, attention: query heads and key-value heads of ``head_dim``,
  causal softmax of ``q k^T / sqrt(head_dim)``, NO rotary; ``W_o``;
* ``E``, latent experts: ``s = sigmoid(u W_r)`` over ALL the model's experts
  in float32; the ``num_experts_per_tok`` largest of ``s + correction
  bias`` are chosen (one group); weights ``s`` at the chosen, over their
  sum (``norm_topk_prob``), ``* routed_scaling_factor``; ``l = u
  W_down_latent``; ``r`` = the weighted sum of the chosen experts' ``W2_e
  relu(W1_e l)^2``, of which only those HELD here (``n_routed_experts``
  from ``first_expert``) are added; ``r W_up_latent`` plus the shared
  expert's ``W2_s relu(W1_s u)^2`` on the row itself.

It reads the *layout* of the program's parameter tree (``blocks``: ``ln1``
stacked over all layers; ``mamba2: {w_in conv_w [taps, C] conv_b dt_bias
a_log skip_scale gate_norm wo}`` over the ``M`` layers; ``attn: {wq wk wv
wo}`` over the ``*`` layers; ``ffn: {gate_w gate_bias w_up w_down [layers,
held, in, out] sw_up sw_down latent_down latent_up}`` over the ``E``
layers; matrices ``[in, out]``) because the weights under test are the
program's. No kernel, no cache, no chunks, no sort or grouped matmul.

``arch["faults"]`` (empty but in the probes and tests that make a mistake
on purpose) names equations to get wrong: ``decay-dropped``,
``wrong-group``, ``skip-dropped``, ``gate-after-norm``, ``norm-whole``,
``taps-reversed``, ``relu-for-relu2``, ``top-k-less-one``, ``scaling-one``,
``rotary-on-attention``.

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

_EXPERT_LEAVES = ("w_up", "w_down")
_KINDS = {"M": "mamba2", "*": "attn", "E": "ffn"}
FAULTS = ("decay-dropped", "wrong-group", "skip-dropped", "gate-after-norm",
          "norm-whole", "taps-reversed", "relu-for-relu2", "top-k-less-one",
          "scaling-one", "rotary-on-attention")


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "nemotron_h":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    pattern = hf["hybrid_override_pattern"]
    assert len(pattern) == hf["num_hidden_layers"] \
        and set(pattern) <= set(_KINDS), "a letter of M * E a layer"
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1 \
            or hf.get("mlp_hidden_act") != "relu2":
        raise ValueError("reference: one routing group and squared-ReLU "
                         "experts are what is written")
    return dict(
        kinds=tuple(_KINDS[c] for c in pattern),
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"], m_heads=hf["mamba_num_heads"],
        m_dim=hf["mamba_head_dim"], groups=hf["n_groups"],
        state=hf["ssm_state_size"], eps=hf["layer_norm_epsilon"],
        top_k=hf["num_experts_per_tok"],
        route_norm=bool(hf.get("norm_topk_prob", True)),
        route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        first_expert=int(hf.get("first_expert", 0)),
        theta=float(hf.get("rope_theta", 10000.0)), faults=frozenset())


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every linear layer of the model: projections, experts, the router,
    the head (one place, so that a probe can read the whole reference in a
    lower precision: ``tools/nemotron_h_probe.py``)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


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


def _mamba2(u, lp, arch):
    S = u.shape[0]
    nh, P, G, N = arch["m_heads"], arch["m_dim"], arch["groups"], \
        arch["state"]
    di, faults = nh * P, arch["faults"]
    zxd = _linear(u, lp["w_in"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * G * N], \
        zxd[:, 2 * di + 2 * G * N:]
    xbc = jax.nn.silu(_short_conv(xbc, lp["conv_w"],
                                  "taps-reversed" in faults) + lp["conv_b"])
    x = xbc[:, :di].reshape(S, nh, P)
    group = jnp.arange(nh) // (nh // G)
    if "wrong-group" in faults:
        group = (group + 1) % G
    B = xbc[:, di:di + G * N].reshape(S, G, N)[:, group]        # [S, nh, N]
    C = xbc[:, di + G * N:].reshape(S, G, N)[:, group]
    delta = jax.nn.softplus(dt + lp["dt_bias"])                 # [S, nh]
    a = jnp.exp(-jnp.exp(lp["a_log"]) * delta)
    if "decay-dropped" in faults:
        a = jnp.ones_like(a)

    def row(state, xs):
        x_t, b_t, c_t, a_t, d_t = xs
        state = a_t[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(row, jnp.zeros((nh, P, N), jnp.float32),
                        (x, B, C, a, delta))
    if "skip-dropped" not in faults:
        y = y + lp["skip_scale"][None, :, None] * x
    y, gate = y.reshape(S, di), jax.nn.silu(z)

    def norm(v):
        v = v.reshape(S, 1 if "norm-whole" in faults else G, -1)
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                              + arch["eps"])
        return v.reshape(S, di) * lp["gate_norm"]

    y = norm(y) * gate if "gate-after-norm" in faults else norm(y * gate)
    return _linear(y, lp["wo"])


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
    q = _linear(u, lp["wq"]).reshape(S, N, D)
    k = _linear(u, lp["wk"]).reshape(S, K, D)
    v = _linear(u, lp["wv"]).reshape(S, K, D)
    if "rotary-on-attention" in arch["faults"]:
        q, k = _rope(q, arch["theta"]), _rope(k, arch["theta"])
    k, v = jnp.repeat(k, N // K, axis=1), jnp.repeat(v, N // K, axis=1)
    pad = -S % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(args):
        qb, lo = args
        qpos = jnp.minimum(lo + jnp.arange(Q_BLOCK), S - 1)  # pad rows
        seen = kpos[None, :] <= qpos[:, None]
        s = jnp.einsum("qnd,snd->nqs", qb, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nqs,snd->qnd", p, v)

    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, N, D),
                              jnp.arange(0, S + pad, Q_BLOCK)))
    return _linear(out.reshape(S + pad, -1)[:S], lp["wo"])


def _act(x, arch):
    x = jax.nn.relu(x)
    return x if "relu-for-relu2" in arch["faults"] else jnp.square(x)


def _route(u, lp, arch):
    """[T, H] -> (routing weight of every token for every expert of the
    MODEL [T, E], zero outside its top-k; the experts chosen [T, k])."""
    faults = arch["faults"]
    scores = jax.nn.sigmoid(_linear(u, lp["gate_w"]))
    _, idx = jax.lax.top_k(scores + lp["gate_bias"], arch["top_k"] - (
        "top-k-less-one" in faults))
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if arch["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if "scaling-one" not in faults:
        w = w * arch["route_scale"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=w.dtype)
    return jnp.einsum("tk,tke->te", w, onehot), idx


def _experts(u, lp, stack, layer, arch):
    """lp: the layer's small leaves in float32; stack: every expert layer's
    ``[layers, experts held, in, out]`` as passed, of which ``layer`` is
    this one's and expert ``e`` the model's ``first_expert + e`` (one
    expert's matrices are read and upcast at a time)."""
    weight, chosen = _route(u, lp, arch)                      # [T, E]
    latent = _linear(u, lp["latent_down"])

    def one_expert(e, r):
        w_up, w_down = (
            jax.lax.dynamic_slice(
                stack[name], (layer, e, 0, 0),
                (1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)
            for name in _EXPERT_LEAVES)
        we = jax.lax.dynamic_slice_in_dim(
            weight, arch["first_expert"] + e, 1, axis=1)
        return r + we * _linear(_act(_linear(latent, w_up), arch), w_down)

    r = jax.lax.fori_loop(0, stack["w_up"].shape[1], one_expert,
                          jnp.zeros_like(latent))
    shared = _linear(_act(_linear(u, lp["sw_up"]), arch), lp["sw_down"])
    return _linear(r, lp["latent_up"]) + shared, chosen


def _layer(x, lp, stack, layer, arch, kind: str):
    """x [S, H] of one sequence; ``lp``: the layer's norm and its one
    sublayer's leaves, flat; ``stack``: the experts' matrices for an ``E``
    layer (``layer`` its index among them). Returns (x, the experts every
    position chose [S, k]; None for a mixer)."""
    lp = _f32(lp)
    u = _rms_norm(x, lp["ln1"]["scale"], arch["eps"])
    if kind == "ffn":
        f, chosen = _experts(u, lp, stack, layer, arch)
        return x + f, chosen
    return x + (_mamba2 if kind == "mamba2" else _attention)(u, lp, arch), \
        None


_layer_jit = jax.jit(_layer, static_argnames=("arch", "kind"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _head_slice(out, x, w, lo):
    return jax.lax.dynamic_update_slice_in_dim(
        out, _linear(x, w.astype(jnp.float32)), lo, axis=2)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


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
        blocks = params["blocks"]
        assert blocks["ln1"]["scale"].shape[0] == len(arch["kinds"]), \
            "depth vs the layer pattern"
        stack = {k: blocks["ffn"][k] for k in _EXPERT_LEAVES}
        rows = []
        for b in range(tokens.shape[0]):
            x = emb[tokens[b]].astype(jnp.float32)
            seen = dict.fromkeys(_KINDS.values(), 0)
            for layer, kind in enumerate(arch["kinds"]):
                nth = seen[kind]
                seen[kind] += 1
                lp = {"ln1": {"scale": blocks["ln1"]["scale"][layer]}}
                lp.update(jax.tree.map(
                    lambda a: a[nth],
                    {k: v for k, v in blocks[kind].items()
                     if k not in _EXPERT_LEAVES}))
                x, chosen = _layer_jit(x, lp, stack if kind == "ffn" else None,
                                       nth, arch=arch, kind=kind)
                if routes is not None and kind == "ffn":
                    routes.append(chosen if at is None
                                  else chosen[jnp.asarray(at)])
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
