"""Paged (block-table) KV forward pass — the FastGen blocked-KV analog.

Parity: reference ``inference/v2/ragged/kv_cache.py:1-208`` (blocked KV with a
host-side allocator) + ``inference/v2/kernels/ragged_ops`` (blocked attention /
KV writes that take a ragged batch of mixed prefill chunks and decode tokens).

TPU design: XLA wants one static shape, so the ragged batch is a FLAT token
batch of fixed budget T: each tick packs decode tokens (one per running
sequence) and prefill chunks (Dynamic SplitFuse) into ``tokens[T]`` with
per-token ``positions[T]`` and ``tables[T, MB]`` (the owning sequence's block
table). The KV pool is ``[L, NB, bs, K, D]``; token (t) writes its K/V at
``pool[tables[t, pos//bs], pos % bs]`` and attends to its first ``pos+1``
cache slots via block gathers. Pad tokens carry an all-zeros table and write
into reserved trash block 0.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import transformer as T

PyTree = Any


def latent_row_width(cfg: T.TransformerConfig) -> int:
    """Columns of a latent pool row: kvr + dr rounded up to the TPU's 128
    lanes, which is what the array occupies in HBM anyway (576 -> 640) and
    what the kernel's block copies and products must be aligned to."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def init_paged_kv(cfg: T.TransformerConfig, n_blocks: int, block_size: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Block pool per layer. Block 0 is the trash block for pad writes.

    MLA models (DeepSeek) pool the LATENTS instead of per-head K/V: one
    row per slot, ``c_kv [kv_lora_rank] ++ k_pe [qk_rope_head_dim]`` (the
    shared post-rope key) ++ zeros up to a lane multiple
    (:func:`latent_row_width`; reference ``ragged/kv_cache.py`` + the v2
    engine's DeepSeek containers). That small row (kvr+dr vs 2·K·D) is
    where paged KV pays off, and one row a position is what lets the
    kernel read each position once."""
    dt = dtype or cfg.compute_dtype
    L = cfg.num_layers
    if cfg.mla:
        return {"latent": jnp.zeros((L, n_blocks, block_size,
                                     latent_row_width(cfg)), dt)}
    shape = (L, n_blocks, block_size, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_attention_reference(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                              tables: jax.Array, lengths: jax.Array,
                              alibi: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Pure-XLA paged attention (the CPU/fallback path; the Pallas kernel in
    ``ops/pallas/paged_attention.py`` computes the same thing without
    materializing the gathered KV).

    q [T, N, D]; pools [NB, bs, K, D]; tables [T, MB]; lengths [T] (= pos+1).
    Token t attends to its sequence's first ``lengths[t]`` cache slots.
    ``alibi``: [N] slopes — cache slot c IS absolute position c, so the
    bias is ``slope · (c − (lengths−1))`` (matches ``cached_attention``).
    """
    Tn, N, D = q.shape
    bs = kpool.shape[1]
    K = kpool.shape[2]
    MB = tables.shape[1]
    kg = kpool[tables]                                   # [T, MB, bs, K, D]
    vg = vpool[tables]
    kg = kg.reshape(Tn, MB * bs, K, D)
    vg = vg.reshape(Tn, MB * bs, K, D)
    if K != N:
        kg = jnp.repeat(kg, N // K, axis=2)
        vg = jnp.repeat(vg, N // K, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    s = jnp.einsum("tnd,tcnd->tnc", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale       # [T, N, ctx]
    if alibi is not None:
        rel = (jnp.arange(MB * bs)[None, :]
               - (lengths[:, None] - 1)).astype(jnp.float32)  # [T, ctx]
        s = s + alibi.astype(jnp.float32)[None, :, None] * rel[:, None, :]
    mask = jnp.arange(MB * bs)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("tnc,tcnd->tnd", p, vg.astype(jnp.float32)).astype(q.dtype)


def grouped_prefill_attention(q: jax.Array, kpool: jax.Array,
                              vpool: jax.Array, group_tables: jax.Array,
                              lengths: jax.Array,
                              alibi: Optional[jax.Array] = None) -> jax.Array:
    """Attention for CHUNK-ALIGNED prefill rows: one block gather per GROUP.

    The planned SplitFuse schedule packs prefill rows so that each
    consecutive group of C rows belongs to ONE sequence (pad rows allowed);
    all rows of a group therefore share a block table and the group gathers
    its KV blocks ONCE — C× less pool traffic and C× fewer table walks than
    the per-token paths, which is what makes prefill ticks run at compute
    speed instead of gather speed (measured 37 ms → ~3 ms per 512-row tick
    on a v5e). q [R, N, D] with R = G·C; group_tables [G, MB];
    lengths [R] (pos+1; pad rows have length ≤ 1 and head=False upstream).
    Cache slot c of a group's gathered context IS absolute position c, so
    causality is just ``c < length(row)`` — same mask rule as the per-token
    reference.
    """
    R, N, D = q.shape
    G, MB = group_tables.shape
    C = R // G
    bs = kpool.shape[1]
    K = kpool.shape[2]
    S = MB * bs
    kg = kpool[group_tables].reshape(G, S, K, D)         # [G, S, K, D]
    vg = vpool[group_tables].reshape(G, S, K, D)
    if K != N:
        kg = jnp.repeat(kg, N // K, axis=2)
        vg = jnp.repeat(vg, N // K, axis=2)
    qg = q.reshape(G, C, N, D)
    lg = lengths.reshape(G, C)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    s = jnp.einsum("gcnd,gsnd->gcns", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale       # [G, C, N, S]
    if alibi is not None:
        rel = (jnp.arange(S)[None, None, :]
               - (lg[:, :, None] - 1)).astype(jnp.float32)     # [G, C, S]
        s = s + alibi.astype(jnp.float32)[None, None, :, None] \
            * rel[:, :, None, :]
    mask = jnp.arange(S)[None, None, None, :] < lg[:, :, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("gcns,gsnd->gcnd", p, vg.astype(jnp.float32))
    return out.reshape(R, N, D).astype(q.dtype)


def _absorbed(q: jax.Array, w_kv_b: jax.Array, cfg: T.TransformerConfig,
              attend: Callable) -> jax.Array:
    """Weight-absorbed MLA around ``attend``: W_uk folds into the query
    and W_uv into the output, so attention runs in latent space against
    pool rows as they are stored. q [T, N, dn+dr] (post-rope) -> the
    latent-space query [T, N, W] (zero beyond kvr+dr, like a pool row);
    ``attend`` returns the attended latents [T, N, kvr]; -> [T, N, dv]."""
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kvr, N = cfg.kv_lora_rank, cfg.num_heads
    dt = q.dtype
    w_kv = w_kv_b.astype(dt).reshape(kvr, N, dn + dv)
    w_uk, w_uv = w_kv[..., :dn], w_kv[..., dn:]
    q_lat = jnp.einsum("tnd,knd->tnk", q[..., :dn], w_uk)    # [T, N, kvr]
    pad = latent_row_width(cfg) - kvr - cfg.qk_rope_head_dim
    q_row = jnp.concatenate(
        [q_lat, q[..., dn:], jnp.zeros(q.shape[:2] + (pad,), dt)], axis=-1)
    return jnp.einsum("tnk,knd->tnd", attend(q_row), w_uv)   # [T, N, dv]


def mla_softmax_scale(cfg: T.TransformerConfig) -> float:
    return cfg.mla_scale_mult / math.sqrt(
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def paged_mla_attention_reference(q: jax.Array, pool: jax.Array,
                                  tables: jax.Array, lengths: jax.Array,
                                  w_kv_b: jax.Array,
                                  cfg: T.TransformerConfig) -> jax.Array:
    """Weight-absorbed MLA attention over the paged LATENT pool (the
    DeepSeek decode trick of ``transformer._mla_absorbed_attention``, paged)
    in plain jnp: the CPU path and the kernel's oracle. It gathers every
    row's whole table, so it is for short tables only.

    q [T, N, dn+dr] (post-rope); pool [NBf, bs, W] (rows ``c_kv ++ k_pe ++
    0``); tables [T, MB]; → [T, N, dv].
    """
    Tn, kvr = q.shape[0], cfg.kv_lora_rank
    bs, MB = pool.shape[1], tables.shape[1]
    dt = q.dtype

    def attend(q_row):
        rows = pool[tables].reshape(Tn, MB * bs, pool.shape[2])
        s = jnp.einsum("tnw,tcw->tnc", q_row, rows).astype(jnp.float32) \
            * mla_softmax_scale(cfg)
        mask = jnp.arange(MB * bs)[None, None, :] < lengths[:, None, None]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1).astype(dt)
        return jnp.einsum("tnc,tck->tnk", p, rows[..., :kvr])

    return _absorbed(q, w_kv_b, cfg, attend)


_EXPERT_LEAVES = ("w_up", "w_down", "w_gate")


def _tick_experts(h: jax.Array, lp: Dict[str, jax.Array],
                  cfg: T.TransformerConfig, valid: jax.Array,
                  stack: Optional[Dict[str, jax.Array]] = None,
                  layer: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """A tick's expert layer on normed rows [T, H], DROPLESS
    (``moe.layer.dropless_moe_ffn``): (output, rows per expert over the
    ``valid`` rows). The experts' matrices are ``lp``'s, or the whole
    layer ``stack``'s with the ``layer`` to use."""
    from deepspeed_tpu.moe.layer import dropless_moe_ffn

    experts = stack or {k: lp[k] for k in _EXPERT_LEAVES if k in lp}
    shared = {k: lp[k] for k in ("sw_up", "sw_down", "sw_gate",
                                 "shared_gate_w") if k in lp}
    return dropless_moe_ffn(
        h, lp["gate_w"], experts, cfg.activation, cfg.moe_top_k,
        score_func=cfg.moe_score_func, route_norm=cfg.moe_route_norm,
        route_scale=cfg.moe_route_scale, shared=shared or None,
        gate_bias=lp.get("gate_bias"), n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group, valid=valid,
        layer=layer if stack else None)


def forward_paged(params: PyTree, tokens: jax.Array, positions: jax.Array,
                  tables: jax.Array, pool: Dict[str, jax.Array],
                  cfg: T.TransformerConfig,
                  attention_fn: Optional[Callable] = None,
                  group_tables: Optional[jax.Array] = None,
                  n_decode: int = 0, with_stats: bool = False):
    """One SplitFuse tick over a flat token batch.

    tokens [T] int32, positions [T] int32, tables [T, MB] int32 (rows shared
    by tokens of the same sequence). Returns (logits [T, vocab] fp32,
    updated pool). Parity: the reference's model-implementation forward over
    a RaggedBatchWrapper (``inference/v2/model_implementations``).

    ``group_tables`` [G, MB] (planned ticks): rows [n_decode:] are
    chunk-aligned — group g of C = (T - n_decode)/G consecutive rows
    belongs to one sequence with table ``group_tables[g]`` and attends via
    :func:`grouped_prefill_attention` (one gather per group); only the
    first ``n_decode`` rows (per-row tables) walk the per-token path. The
    KV WRITE path always uses the per-row tables.

    MLA (DeepSeek) models pool latents and attend weight-absorbed: with
    the latent instantiation of the Pallas kernel when ``attention_fn`` is
    a kernel (any: which one is ``cfg``'s to say, not the caller's), with
    :func:`paged_mla_attention_reference` otherwise; ALiBi models
    (BLOOM/Falcon) bias the paged scores by head slope × relative position.

    Expert layers run dropless (:func:`_tick_experts`). ``with_stats``
    adds a third result, ``{"expert_rows": [expert layers, E] int32}``
    (rows each expert got from the tick's real rows; ``{}`` for a model
    without experts).
    """
    if cfg.mla:
        out = _forward_paged_mla(
            params, tokens, positions, tables, pool, cfg,
            use_kernel=attention_fn not in (None, paged_attention_reference))
        return out if with_stats else out[:2]
    attention_fn = attention_fn or paged_attention_reference
    alibi = None
    if cfg.pos_emb == "alibi":
        # the Pallas kernel has no bias input yet — ALiBi ticks use the
        # XLA reference path (correct, rectangular-gather cost)
        attention_fn = paged_attention_reference
        alibi = T.alibi_slopes(cfg.num_heads) * cfg.alibi_bias_scale
    dt = cfg.compute_dtype
    Tn = tokens.shape[0]
    bs = pool["k"].shape[2]

    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]             # [T, H]
        if cfg.pos_emb == "learned":
            x = x + params["pos_emb"].astype(dt)[positions]
        if cfg.emb_norm:
            x = T._norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)

    max_pos = pool["k"].shape[1] * bs
    cos_t = sin_t = None
    if cfg.pos_emb == "rope":
        cos_t, sin_t = T.rope_table(max_pos, cfg.rope_dim, cfg.rope_theta,
                                    cfg.rope_scaling_dict)
    block_idx = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1)[:, 0]  # [T]
    offsets = positions % bs
    lengths = positions + 1
    valid = tables[:, 0] > 0     # a pad row's table is all trash block

    def ffn(h2, lp):
        if cfg.n_experts:
            return _tick_experts(h2, lp, cfg, valid)
        return T._ffn(h2, lp, cfg)[0], None

    # The pool rides the layer scan as a FLAT [L*NB, bs, K, D] carry that is
    # scattered in place (layer l owns block range [l*NB, (l+1)*NB)); the
    # attention kernel gathers through layer-offset tables, reading only the
    # listed blocks. Threading per-layer slices as scan xs→ys (the naive
    # layout) re-stacks the ENTIRE pool every call — measured 25 ms/tick at
    # 512 blocks inside a decode scan, linear in pool size — where the
    # in-place carry touches only the written rows.
    L, NB = pool["k"].shape[0], pool["k"].shape[1]
    flat = (L * NB,) + pool["k"].shape[2:]

    def body(carry, lp):
        from deepspeed_tpu.ops.quantization import dequant_params

        x, pk, pv, li = carry
        lp = dequant_params(lp, dt)   # weight-only quant: per-layer dequant
        with jax.named_scope("attn"):
            h = T._norm(x, lp["ln1"], cfg.norm, cfg.norm_eps)

            def proj(name, shape):
                w = lp[f"w{name}"].astype(dt)
                out = h @ w
                if (cfg.attn_bias_enabled if name in ("q", "k", "v")
                        else cfg.use_bias):
                    out = out + lp[f"b{name}"].astype(dt)
                return out.reshape(shape)

            q = proj("q", (Tn, cfg.num_heads, cfg.head_dim))
            k = proj("k", (Tn, cfg.kv_heads, cfg.head_dim))
            v = proj("v", (Tn, cfg.kv_heads, cfg.head_dim))
            if cfg.qk_norm:
                q = T._head_rmsnorm(q, lp["q_norm"], cfg.norm_eps)
                k = T._head_rmsnorm(k, lp["k_norm"], cfg.norm_eps)
            if cfg.pos_emb == "rope":
                q = T.apply_rope_at(q[None], cos_t, sin_t, positions[None])[0]
                k = T.apply_rope_at(k[None], cos_t, sin_t, positions[None])[0]
            # blocked KV write (reference ragged_ops KV-copy kernels): token t →
            # pool[l*NB + block_idx[t], offsets[t]]. Pad tokens hit this layer's
            # trash block (block 0 of its range — never allocated).
            base = li * NB
            pk = pk.at[base + block_idx, offsets].set(k.astype(pk.dtype),
                                                      mode="drop")
            pv = pv.at[base + block_idx, offsets].set(v.astype(pv.dtype),
                                                      mode="drop")

            if group_tables is not None:
                parts = []
                if n_decode:
                    parts.append(
                        attention_fn(q[:n_decode], pk, pv,
                                     tables[:n_decode] + base,
                                     lengths[:n_decode],
                                     **({"alibi": alibi} if alibi is not None
                                        else {})))
                parts.append(grouped_prefill_attention(
                    q[n_decode:], pk, pv, group_tables + base,
                    lengths[n_decode:], alibi=alibi))
                attn = jnp.concatenate(parts, axis=0) if n_decode else parts[0]
            elif alibi is not None:
                attn = attention_fn(q, pk, pv, tables + base, lengths,
                                    alibi=alibi)                    # [T, N, D]
            else:
                attn = attention_fn(q, pk, pv, tables + base, lengths)
            attn = attn.reshape(Tn, cfg.num_heads * cfg.head_dim)
            attn_out = attn @ lp["wo"].astype(dt)
            if cfg.use_bias:
                attn_out = attn_out + lp["bo"].astype(dt)
        with jax.named_scope("mlp"):
            if cfg.parallel_block:
                h2 = h if cfg.shared_parallel_norm else \
                    T._norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
                down, rows = ffn(h2, lp)
                return (x + attn_out + down, pk, pv, li + 1), rows
            x = x + attn_out
            h2 = T._norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
            down, rows = ffn(h2, lp)
            return (x + down, pk, pv, li + 1), rows

    carry0 = (x, pool["k"].reshape(flat), pool["v"].reshape(flat),
              jnp.int32(0))
    (x, new_k, new_v, _), rows = lax.scan(body, carry0, params["blocks"])
    new_k = new_k.reshape(pool["k"].shape)
    new_v = new_v.reshape(pool["v"].shape)
    with jax.named_scope("lm_head"):
        x = T._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        head = T._lm_head_of(params, cfg)
        logits = T.head_matmul(x, head.astype(x.dtype))
        if cfg.lm_head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
    new_pool = {"k": new_k, "v": new_v}
    if with_stats:
        return logits, new_pool, \
            {} if rows is None else {"expert_rows": rows}
    return logits, new_pool


def _forward_paged_mla(params: PyTree, tokens: jax.Array,
                       positions: jax.Array, tables: jax.Array,
                       pool: Dict[str, jax.Array], cfg: T.TransformerConfig,
                       use_kernel: bool):
    """MLA SplitFuse tick: write each row's latent (``c_kv ++ k_pe``) into
    the paged pool and attend weight-absorbed (same flat in-place pool
    carry as the dense path; same math as the v1 engine's latent-cache
    decode). The stack is one scan per segment of ``cfg.segments`` (the
    leading dense layers, then the expert layers), the pool's layers in
    the same order. Returns (logits, pool, stats) as ``forward_paged``
    with ``with_stats``."""
    dt = cfg.compute_dtype
    Tn = tokens.shape[0]
    lat = pool["latent"]
    L, NB, bs, W = lat.shape

    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]
        if cfg.emb_norm:
            x = T._norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)

    cos_t, sin_t = T.rope_table(NB * bs, cfg.qk_rope_head_dim,
                                cfg.rope_theta, cfg.rope_scaling_dict)

    def rope_fn(v):                                   # v [T, 1, n, dr]
        return T.apply_rope_at(v, cos_t, sin_t, positions[:, None])

    block_idx = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1)[:, 0]
    offsets = positions % bs
    lengths = positions + 1
    valid = tables[:, 0] > 0     # a pad row's table is all trash block
    row_pad = jnp.zeros(
        (Tn, W - cfg.kv_lora_rank - cfg.qk_rope_head_dim), lat.dtype)

    if use_kernel:
        from deepspeed_tpu.ops.pallas.paged_attention import \
            latent_paged_attention

        def attend(q, plat, rows_tables, w_kv_b):
            return _absorbed(q, w_kv_b, cfg, lambda q_row:
                             latent_paged_attention(
                                 q_row, plat, rows_tables, lengths,
                                 cfg.kv_lora_rank, mla_softmax_scale(cfg)))
    else:
        def attend(q, plat, rows_tables, w_kv_b):
            return paged_mla_attention_reference(q, plat, rows_tables,
                                                 lengths, w_kv_b, cfg)

    def make_body(seg: T.TransformerConfig, first: int, stack):
        def body(carry, lp):
            from deepspeed_tpu.ops.quantization import dequant_params

            x, plat, li = carry
            lp = dequant_params(lp, dt)
            with jax.named_scope("attn"):
                h = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
                hB = h[:, None, :]                        # [T, 1, H]
                q = T._mla_q(hB, lp, seg, rope_fn)[:, 0]  # [T, N, dn+dr]
                c_kv, k_pe = T._mla_latents(hB, lp, seg, rope_fn)
                row = jnp.concatenate(
                    [c_kv[:, 0].astype(plat.dtype),
                     k_pe[:, 0, 0].astype(plat.dtype), row_pad], axis=-1)
                base = li * NB
                plat = plat.at[base + block_idx, offsets].set(row,
                                                              mode="drop")
                attn = attend(q, plat, tables + base, lp["wkv_b"])
                attn = attn.reshape(Tn, seg.num_heads * seg.v_head_dim)
                x = x + attn @ lp["wo"].astype(dt)
            h2 = T._norm(x, lp["ln2"], seg.norm, seg.norm_eps)
            if seg.n_experts:
                down, rows = _tick_experts(h2, lp, seg, valid, stack,
                                           li - first)
            else:
                with jax.named_scope("mlp"):
                    down, rows = T._ffn(h2, lp, seg)[0], None
            return (x + down, plat, li + 1), rows

        return body

    carry = (x, lat.reshape(L * NB, bs, W), jnp.int32(0))
    stats = {}
    first = 0
    for key, seg in cfg.segments:
        # the experts' matrices stay out of the scan's sliced operands: the
        # grouped matmul takes the stack whole (``moe.layer.grouped_dot``);
        # quantised leaves ({"q", "scale", ...}) are dequantised a layer at
        # a time and stay in
        stack = {k: v for k, v in params[key].items() if seg.n_experts
                 and k in _EXPERT_LEAVES and hasattr(v, "ndim")}
        xs = {k: v for k, v in params[key].items() if k not in stack}
        carry, rows = lax.scan(make_body(seg, first, stack), carry, xs)
        first += seg.num_layers
        if rows is not None:
            stats["expert_rows"] = rows
    x, new_lat, _ = carry
    with jax.named_scope("lm_head"):
        x = T._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        head = T._lm_head_of(params, cfg)
        logits = T.head_matmul(x, head.astype(x.dtype))
        if cfg.lm_head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
    return logits, {"latent": new_lat.reshape(lat.shape)}, stats
