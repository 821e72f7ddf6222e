"""Plain reference: the ``ouro`` family's decoder (a LOOPED language model:
the Llama block under four RMSNorms a layer, the whole stack run several
times over the same weights, an exit gate that chooses the pass whose state
feeds the head), forward pass and next-token loss, in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``.

Written from the family's published description as ISSUE 55 sets it out
(the paper "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, and the published ``modeling_ouro.py``), not from the
program: it imports nothing of ``deepspeed_tpu``. The equations (``x`` the
residual stream, R = ``total_ut_steps`` passes, L layers, RMSNorm with eps
``rms_norm_eps`` everywhere, rotary of ``rope_theta`` over the whole head,
no bias in the layers, the head untied):

    x = E[token]
    for t in 0 .. R-1:                         # the SAME L layers every pass
        for l in 0 .. L-1:
            q, k, v = rope(Wq h), rope(Wk h), Wv h      h = RMS(x; ln1_l)
            a = softmax(q K^T / sqrt(D), causal) V      # pass t's OWN keys
            x = x + RMS(a Wo; ln1_post_l)               #  and values
            m = Wdown(silu(Wgate h2) * (Wup h2))        h2 = RMS(x; ln2_l)
            x = x + RMS(m; ln2_post_l)
        x = RMS(x; final_norm);  h_t = x       # after EVERY pass; feeds the next
        lambda_t = sigmoid(w_exit . h_t + b_exit)
    p_t = lambda_t prod_{s<t} (1 - lambda_s)  (t < R-1);  p_{R-1} = prod_{s<R-1} (1 - lambda_s)
    t* = first t with sum_{s<=t} p_s >= early_exit_threshold, else R-1
    logits = W_head h_{t*}                     # h_t is normed already

Every pass runs for every token; the exit rule only chooses which pass's
state feeds the head, a token at a time.

It reads the *layout* of the program's parameter tree (stacked ``blocks``
with ``wq wk wv wo w_up w_gate w_down`` stored ``[in, out]``, ``ln1
ln1_post ln2 ln2_post final_norm`` as ``{"scale"}``, ``tok_emb``,
``lm_head``, ``exit_gate: {"w" [H, 1], "b" [1]}``) because the weights
under test are the program's own. No kernel, no cache, no scan.

``arch["faults"]`` (empty but in the probes and tests that make a mistake
on purpose) names equations to get wrong (``FAULTS``): ``three-passes``
(one pass fewer), ``no-loop-norm`` (the final norm after the last pass
alone), ``second-head-norm`` (the chosen state normed again at the head),
``previous-pass-cache`` (pass t > 0 attends to pass t-1's keys and values:
a cache layer a layer, read before it is written), ``last-pass-cache``
(earlier positions' keys and values are the LAST pass's for every pass:
a cache layer a layer that every pass writes, as a decode step finds it;
the last pass's are taken from a first, right, sweep), ``post-norms-dropped``
(``ln1_post`` / ``ln2_post`` left out), ``post-norm-before-wo`` (``RMS(a;
ln1_post) Wo`` for ``RMS(a Wo; ln1_post)``), ``head-from-pass-0``,
``gate-bias-dropped``.

Departures from the published file, each deliberate: queries are met a
block at a time (a sequence padded to whole blocks, the added rows cut off
again) and the head a slice of the vocabulary at a time, weights upcast a
layer at a time (so that the check fits beside a serving engine);
the published forward returns every pass's logits and takes its threshold
as an argument too: here the states are kept, the chosen one alone meets
the head, and the threshold is the configuration's.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

Q_BLOCK = 128
VOCAB_BLOCK = 8192

FAULTS = ("three-passes", "no-loop-norm", "second-head-norm",
          "previous-pass-cache", "last-pass-cache", "post-norms-dropped",
          "post-norm-before-wo", "head-from-pass-0", "gate-bias-dropped")


def arch_from_config(config: Dict[str, Any], hf: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The few facts the equations need, from the source keys as run."""
    if config["model_type"] != "ouro":
        raise ValueError(f"no reference for model_type "
                         f"{config['model_type']!r}")
    heads = hf["num_attention_heads"]
    return dict(
        heads=heads, kv_heads=hf.get("num_key_value_heads") or heads,
        head_dim=hf["hidden_size"] // heads, eps=hf["rms_norm_eps"],
        theta=float(hf["rope_theta"]), passes=int(hf["total_ut_steps"]),
        threshold=float(hf.get("early_exit_threshold", 1.0)),
        faults=frozenset())


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _linear(x, w):
    """Every linear layer of the model: projections, the feed-forward part,
    the head (one place, so that a probe can read the whole reference in a
    lower precision: ``tools/ouro_probe.py``)."""
    return x @ w


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x [S, N, D] at positions 0 .. S-1, the whole head rotated
    (``rotate_half`` convention)."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attention(q, k, v, k_before, v_before):
    """Causal softmax attention, q [S, N, D]; k, v [S, K, D] are what a row
    finds at its OWN position, ``k_before`` / ``v_before`` at the positions
    before it (the same arrays, but in the mistakes that read another
    pass's cache)."""
    S, N, D = q.shape
    rep = N // k.shape[1]
    k, v, k_before, v_before = (jnp.repeat(a, rep, axis=1) if rep > 1 else a
                                for a in (k, v, k_before, v_before))
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("qnd,knd->nqk", qb, k_before) / math.sqrt(D)
        own = jnp.einsum("qnd,qnd->nq", qb, k[lo:lo + Q_BLOCK]) \
            / math.sqrt(D)
        s = jnp.where((kpos[None, :] < qpos[:, None])[None], s, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1),
                           axis=-1)
        outs.append(jnp.einsum("nqk,knd->qnd", p[..., :-1], v_before)
                    + p[..., -1].T[..., None] * v[lo:lo + Q_BLOCK])
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("arch",))
def _layer(x, lp, before, arch):
    """One application of one layer on x [S, H]: (x, this pass's keys,
    its values). ``before``: the keys and values the rows find at EARLIER
    positions, or None: this application's own."""
    lp = _f32(lp)          # one layer of the caller's weights, upcast here
    S, H = x.shape
    N, K, D = arch["heads"], arch["kv_heads"], arch["head_dim"]
    eps, faults = arch["eps"], arch["faults"]
    post = "post-norms-dropped" not in faults
    h = _rms_norm(x, lp["ln1"]["scale"], eps)
    q = _rope(_linear(h, lp["wq"]).reshape(S, N, D), arch["theta"])
    k = _rope(_linear(h, lp["wk"]).reshape(S, K, D), arch["theta"])
    v = _linear(h, lp["wv"]).reshape(S, K, D)
    kb, vb = (k, v) if before is None else before
    a = _attention(q, k, v, kb, vb).reshape(S, N * D)
    if "post-norm-before-wo" in faults:
        a = _linear(_rms_norm(a, lp["ln1_post"]["scale"], eps), lp["wo"])
    else:
        a = _linear(a, lp["wo"])
        if post:
            a = _rms_norm(a, lp["ln1_post"]["scale"], eps)
    x = x + a
    h2 = _rms_norm(x, lp["ln2"]["scale"], eps)
    m = _linear(jax.nn.silu(_linear(h2, lp["w_gate"]))
                * _linear(h2, lp["w_up"]), lp["w_down"])
    if post:
        m = _rms_norm(m, lp["ln2_post"]["scale"], eps)
    return x + m, k, v


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _passes(params, tokens, arch, last_pass_cache=None):
    """The passes' normed states of one sequence, [R, S, H], and the last
    pass's keys and values a layer."""
    faults = arch["faults"]
    R = arch["passes"] - ("three-passes" in faults)
    fnorm = _f32(params["final_norm"])["scale"]
    depth = jax.tree.leaves(params["blocks"])[0].shape[0]
    # whole blocks of queries (a row sees nothing behind it: the rows
    # added are cut off again), so that sequences of nearby lengths share
    # one compiled layer
    S = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -S % Q_BLOCK))
    x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
    states, kept = [], [None] * depth
    for t in range(R):
        for layer in range(depth):
            lp = jax.tree.map(lambda a: a[layer], params["blocks"])
            before = None
            if "previous-pass-cache" in faults and t > 0:
                before = kept[layer]
            if last_pass_cache is not None:
                before = last_pass_cache[layer]
            x, k, v = _layer(x, lp, before, arch=arch)
            # one layer at a time on the device too: a loop that runs ahead
            # of it holds a slice of weights for every call it has queued
            # (16.89 of the chip's 16.91 GB beside the serving engine)
            x.block_until_ready()
            kept[layer] = (k, v)
        if "no-loop-norm" not in faults or t == R - 1:
            x = _rms_norm(x, fnorm, arch["eps"])
        states.append(x[:S])
    return jnp.stack(states), kept


def exit_distribution(params, states, arch):
    """The exit rule on the passes' states [R, S, H]: (the distribution
    [S, R], the chosen pass [S])."""
    if states.shape[0] == 1:            # one pass: no gate, nothing to choose
        return jnp.ones(states.shape[1:2] + (1,), jnp.float32), \
            jnp.zeros(states.shape[1:2], jnp.int32)
    gate = _f32(params["exit_gate"])
    logit = states @ gate["w"][:, 0]
    if "gate-bias-dropped" not in arch["faults"]:
        logit = logit + gate["b"]
    lam = jax.nn.sigmoid(logit)                           # [R, S]
    R = lam.shape[0]
    remaining, pdf = jnp.ones_like(lam[0]), []
    for t in range(R):
        if t < R - 1:
            pdf.append(lam[t] * remaining)
            remaining = remaining * (1.0 - lam[t])
        else:
            pdf.append(remaining)
    pdf = jnp.stack(pdf, axis=-1)                         # [S, R]
    reached = jnp.cumsum(pdf, axis=-1) >= jnp.float32(arch["threshold"])
    chosen = jnp.where(reached.any(axis=-1), jnp.argmax(reached, axis=-1),
                       R - 1)
    if "head-from-pass-0" in arch["faults"]:
        chosen = jnp.zeros_like(chosen)
    return pdf, chosen


def forward(params, tokens, arch: Dict[str, Any],
            at: Optional[Sequence[int]] = None) -> Dict[str, Any]:
    """tokens [B, S] int32 -> ``logits`` [B, S or len(at), V] float32 (the
    positions ``at`` alone where given), ``exit_pdf`` [B, ., R] and
    ``chosen`` [B, .], the pass whose state fed the head."""
    arch = _Frozen(arch)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        head = params["lm_head"]
        out = {"logits": [], "exit_pdf": [], "chosen": []}
        for row in range(tokens.shape[0]):
            states, kept = _passes(params, tokens[row], arch)
            if "last-pass-cache" in arch["faults"]:
                states, _ = _passes(params, tokens[row], arch, kept)
            if at is not None:
                states = states[:, jnp.asarray(at)]
            pdf, chosen = exit_distribution(params, states, arch)
            h = jnp.take_along_axis(
                states, chosen[None, :, None], axis=0)[0]   # [S, H]
            if "second-head-norm" in arch["faults"]:
                h = _rms_norm(h, _f32(params["final_norm"])["scale"],
                              arch["eps"])
            out["logits"].append(jnp.concatenate([
                _linear(h, jnp.asarray(head[:, lo:lo + VOCAB_BLOCK],
                                       jnp.float32))
                for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1))
            out["exit_pdf"].append(pdf)
            out["chosen"].append(chosen)
        return {k: jnp.stack(v) for k, v in out.items()}


def forward_logits(params, tokens, arch: Dict[str, Any],
                   at: Optional[Sequence[int]] = None):
    """tokens [B, S] int32 -> logits [B, S or len(at), V] float32."""
    return forward(params, tokens, arch, at)["logits"]


def next_token_loss(params, tokens, arch: Dict[str, Any]) -> float:
    """Mean cross-entropy of token t+1 given tokens <= t on the CHOSEN
    pass's logits, over every position of every sequence, one sequence at
    a time. (The published training objective weights every pass's loss by
    the exit distribution and adds an entropy term whose coefficient the
    configuration does not carry: not this.)"""
    total, count = 0.0, 0
    tokens = jnp.asarray(tokens)
    for row in range(tokens.shape[0]):
        logits = forward_logits(params, tokens[row:row + 1], arch)[0, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = tokens[row, 1:]
        total += float(-jnp.take_along_axis(logp, tgt[:, None], axis=1).sum())
        count += int(tgt.shape[0])
    return total / count
