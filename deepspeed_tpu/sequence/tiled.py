"""Tiled sequence compute — ALST's memory-capping tricks, the XLA way.

Parity: reference ``runtime/sequence_parallel/ulysses_sp.py`` (``TiledMLP``
:943, ``TiledFusedLogitsLoss`` :1065, ``sequence_tiled_compute`` :720) — for
arbitrary-length training the sequence dim is processed in tiles so that
position-wise layers (MLP, logits+loss) never materialize the full [B, S, ...]
activation. Here each helper is a ``lax.scan`` over sequence tiles with
``jax.checkpoint`` on the tile body — the backward recomputes one tile at a
time, giving the same peak-memory cap as the reference's autograd-function
shards, but fused into the surrounding XLA program.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def _split_tiles(x: jax.Array, num_tiles: int, axis: int) -> jax.Array:
    S = x.shape[axis]
    if S % num_tiles != 0:
        raise ValueError(f"seq len {S} not divisible by num_tiles {num_tiles}")
    tile = S // num_tiles
    x = jnp.moveaxis(x, axis, 0)
    return x.reshape((num_tiles, tile) + x.shape[1:])


def _merge_tiles(x: jax.Array, axis: int) -> jax.Array:
    x = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
    return jnp.moveaxis(x, 0, axis)


def sequence_tiled_compute(fn: Callable[[jax.Array], jax.Array], x: jax.Array,
                           num_tiles: int, axis: int = 1,
                           remat: bool = True) -> jax.Array:
    """Apply a position-wise ``fn`` over sequence tiles (TiledMLP analog).

    ``fn`` must be position-wise along ``axis`` (MLP, norm, elementwise...)."""
    if num_tiles <= 1:
        return fn(x)
    tiles = _split_tiles(x, num_tiles, axis)  # [T, tile, ...] (axis moved to front)

    def body(_, t):
        # t: [tile, ...]; restore the tile's dims to fn's expected layout
        return None, jnp.moveaxis(fn(jnp.moveaxis(t, 0, axis)), axis, 0)

    if remat:
        body = jax.checkpoint(body)
    _, out = lax.scan(body, None, tiles)      # [T, tile, ...]
    return _merge_tiles(out, axis)


def tiled_lm_loss(hidden: jax.Array, head: jax.Array, tokens: jax.Array,
                  loss_mask: Optional[jax.Array] = None,
                  num_tiles: int = 8, remat: bool = True,
                  logits_divisor: float = 1.0) -> jax.Array:
    """Next-token CE without materializing [B, S, vocab] logits
    (``logits_divisor``: ``TransformerConfig.logits_divisor``).

    Parity: ``TiledFusedLogitsLoss`` (``ulysses_sp.py:1065``). hidden: [B,S,H]
    (pre-head final activations), head: [H,V]. Scans sequence tiles, computing
    per-tile logits + log-softmax; backward rematerializes one tile at a time.
    """
    B, S, H = hidden.shape
    # shift: predict token t+1 from position t
    hid = hidden[:, :-1]
    tgt = tokens[:, 1:]
    mask = None if loss_mask is None else loss_mask[:, 1:].astype(jnp.float32)
    Sm = S - 1
    pad = (-Sm) % num_tiles
    if pad:
        hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None else jnp.ones((B, Sm), jnp.float32),
                       ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((B, Sm), jnp.float32)

    hid_t = _split_tiles(hid, num_tiles, 1)    # [T, tile, B, H]
    tgt_t = _split_tiles(tgt, num_tiles, 1)    # [T, tile, B]
    mask_t = _split_tiles(mask, num_tiles, 1)  # [T, tile, B]
    head_c = head.astype(hidden.dtype)

    def tile_body(carry, operand):
        from deepspeed_tpu.models.transformer import (divide_logits,
                                                      head_matmul)

        h, t, mk = operand                     # [tile,B,H], [tile,B], [tile,B]
        logits = divide_logits(head_matmul(h, head_c),   # [tile, B, V] fp32
                               logits_divisor)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = (logz - picked) * mk
        loss_sum, count = carry
        return (loss_sum + jnp.sum(nll), count + jnp.sum(mk)), None

    if remat:
        tile_body = jax.checkpoint(tile_body)
    (loss_sum, count), _ = lax.scan(
        tile_body, (jnp.float32(0.0), jnp.float32(0.0)), (hid_t, tgt_t, mask_t))
    return loss_sum / jnp.maximum(count, 1.0)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, segment_mask=None,
                      num_chunks: int = 4, remat: bool = True) -> jax.Array:
    """FPDT-style query-chunked attention (``sequence/fpdt_layer.py:545`` analog).

    Scans over Q chunks against the full K/V so peak score memory is
    [B, N, S/chunks, S]; with ``remat`` the backward recomputes per chunk. The
    reference offloads KV chunks to host; on TPU the scan + remat achieves the
    memory cap without host traffic (XLA keeps K/V resident in HBM).
    """
    import math

    if segment_mask is not None:
        raise NotImplementedError("segment_mask unsupported in chunked attention")
    B, S, N, D = q.shape
    K = k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    if num_chunks <= 1 or S % num_chunks != 0:
        from deepspeed_tpu.models.transformer import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal)
    C = S // num_chunks
    scale = 1.0 / math.sqrt(D)
    qc = q.reshape(B, num_chunks, C, N, D).transpose(1, 0, 2, 3, 4)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kv_pos = jnp.arange(S)

    def chunk_body(carry, operand):
        i, qi = operand                        # qi: [B, C, N, D]
        scores = jnp.einsum("bcnd,btnd->bnct", qi.astype(jnp.float32), kf) * scale
        if causal:
            q_pos = i * C + jnp.arange(C)
            mask = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bnct,btnd->bcnd", probs, vf)
        return carry, out.astype(q.dtype)

    if remat:
        chunk_body = jax.checkpoint(chunk_body)
    _, chunks = lax.scan(chunk_body, None, (jnp.arange(num_chunks), qc))
    return chunks.transpose(1, 0, 2, 3, 4).reshape(B, S, N, D)


def _memory_constraint(x: jax.Array, space: "jax.memory.Space") -> jax.Array:
    """Move an intermediate to a memory space (jit-traceable device_put).
    The CPU test backend has one memory, so it is the identity there."""
    if jax.default_backend() != "tpu":
        return x
    return jax.device_put(x, space)


def fpdt_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True, segment_mask=None,
                   num_chunks: int = 4, kv_chunks: int = 4,
                   offload_kv: bool = True, remat: bool = True) -> jax.Array:
    """FPDT attention with host-offloaded KV (``sequence/fpdt_layer.py``
    ``_FPDTGPUOffloadingAttentionImpl_`` :545 analog).

    The full K/V live in **pinned host memory**; the scan walks (q-chunk,
    kv-chunk) pairs with online-softmax accumulation, so device HBM holds one
    [B, C, N, D] KV chunk at a time — the multi-million-token recipe. XLA
    emits the host↔device DMAs from the memory-kind constraints and its
    scheduler overlaps the next chunk's fetch with the current chunk's
    matmuls (the reference's double-buffered prefetch, compiler-scheduled).
    On non-TPU backends the host constraint is an identity and the math is
    unchanged.
    """
    import math

    if segment_mask is not None:
        raise NotImplementedError("segment_mask unsupported in FPDT attention")
    B, S, N, D = q.shape
    K = k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    if (num_chunks <= 1 or S % num_chunks or kv_chunks <= 1
            or S % kv_chunks):
        return chunked_attention(q, k, v, causal=causal,
                                 num_chunks=max(num_chunks, 1), remat=remat)
    C = S // num_chunks
    CK = S // kv_chunks
    scale = 1.0 / math.sqrt(D)

    kh = k.reshape(B, kv_chunks, CK, N, D).transpose(1, 0, 2, 3, 4)
    vh = v.reshape(B, kv_chunks, CK, N, D).transpose(1, 0, 2, 3, 4)
    if offload_kv:
        kh = _memory_constraint(kh, jax.memory.Space.Host)
        vh = _memory_constraint(vh, jax.memory.Space.Host)
    qc = q.reshape(B, num_chunks, C, N, D).transpose(1, 0, 2, 3, 4)

    def q_body(_, operand):
        qi_idx, qi = operand                      # qi: [B, C, N, D]
        q32 = qi.astype(jnp.float32)
        q_pos = qi_idx * C + jnp.arange(C)

        def kv_body(carry, kv_operand):
            acc, m, l = carry
            kj_idx, kj, vj = kv_operand           # [B, CK, N, D]
            if offload_kv:
                # pull ONE chunk into device HBM (the streamed fetch)
                kj = _memory_constraint(kj, jax.memory.Space.Device)
                vj = _memory_constraint(vj, jax.memory.Space.Device)
            kj = kj.astype(jnp.float32)
            vj = vj.astype(jnp.float32)
            s = jnp.einsum("bcnd,btnd->bnct", q32, kj) * scale
            if causal:
                kv_pos = kj_idx * CK + jnp.arange(CK)
                mask = q_pos[:, None] >= kv_pos[None, :]
                s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bnct,btnd->bnc d".replace(" ", ""), p, vj)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, N, C, D), jnp.float32),
                jnp.full((B, N, C, 1), -1e30, jnp.float32),
                jnp.zeros((B, N, C, 1), jnp.float32))
        (acc, m, l), _ = lax.scan(
            kv_body, init, (jnp.arange(kv_chunks), kh, vh))
        out = acc / jnp.maximum(l, 1e-30)
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, C, N, D]

    if remat:
        q_body = jax.checkpoint(q_body)
    _, chunks = lax.scan(q_body, None, (jnp.arange(num_chunks), qc))
    return chunks.transpose(1, 0, 2, 3, 4).reshape(B, S, N, D)
