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

import contextlib
import math
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.models import hybrid as HY
from deepspeed_tpu.models import transformer as T

PyTree = Any


def latent_row_width(cfg: T.TransformerConfig) -> int:
    """Columns of a latent pool row: kvr + dr rounded up to the TPU's 128
    lanes, which is what the array occupies in HBM anyway (576 -> 640) and
    what the kernel's block copies and products must be aligned to."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def ring_blocks(cfg: T.TransformerConfig, block_size: int,
                max_run: int) -> int:
    """Blocks of a window layer's ring, per sequence: a tick writes a
    sequence's rows before any of them attends, so the ring holds the
    window and the longest run of rows one sequence can have in a tick."""
    return -(-(cfg.attn_window + max_run) // block_size)


def kv_lane_pack(cfg: T.TransformerConfig) -> int:
    """KV heads that lie side by side in one row of a standard-block pool:
    2 where a head is 64 wide, so that a row fills the TPU's 128 lanes
    (``[bs, K/2, 128]``), else 1. A block ``[bs, K, 64]`` would be padded
    to the lanes in HBM (twice the bytes held and fetched), and Mosaic
    refuses the kernel's strided load of a slot whose last dim is not 128
    (found by compiling for a described v5e, PERF.md, PR 37). Heads ``2g``
    and ``2g + 1`` are one key of 128 columns, the values likewise; a
    query of head ``2g`` is ``[q | 0]``, of ``2g + 1`` ``[0 | q]``, and its
    output its own half (:func:`_lane_packed`, as differential attention's
    ``hybrid.paired_queries``): no head moves, the products are twice as
    wide, which a kernel bound by its bytes does not feel."""
    return 2 if cfg.standard_blocks and cfg.head_dim == 64 \
        and cfg.kv_heads % 2 == 0 else 1


def _lane_packed(q: jax.Array, kv_heads: int) -> Tuple[jax.Array, Callable]:
    """Queries q [T, N, D] against KV heads stored two to a row
    (:func:`kv_lane_pack`): (the queries [T, N, 2 D], zero in the half of
    the other head of the pair; a function that takes each head's own half
    of the attended values [T, N, 2 D] -> [T, N, D])."""
    N, D = q.shape[1:]
    odd = (jnp.arange(N) // (N // kv_heads)) % 2 == 1
    return HY.paired_queries(q, odd), lambda o: jnp.where(
        odd[None, :, None], o[..., D:], o[..., :D])


def init_paged_kv(cfg: T.TransformerConfig, n_blocks: int, block_size: int,
                  dtype=None, state_slots: int = 0, max_run: int = 0
                  ) -> Dict[str, jax.Array]:
    """What a model keeps of its sequences between ticks, as a dict of
    arrays each ``[layers that keep it, rows, ...]`` (``forward_paged``
    carries every one flat, ``[layers * rows, ...]``, and a layer owns the
    range that starts at ``layer * rows``). Three kinds:

    * a BLOCK pool ``[L, NB, bs, ...]``, which grows with a sequence a
      block at a time through its block table; block 0 is the trash block
      pad rows write into. ``{"k", "v"}`` per head, or ``{"latent"}``;
    * a RING ``[L, (slots + 1) * RB, bs, ...]``: ``RB`` blocks
      (:func:`ring_blocks`) a sequence slot, position ``p`` in block
      ``(p // bs) % RB`` of its slot's, whatever the sequence's length;
    * STATE ``[L, slots + 1, ...]``: one row a sequence slot; a
      convolution's last inputs ``[L x inputs x (slots + 1), channels]``,
      a row an input of a slot (:func:`_conv_store`).

    A sequence's slot is its first block's id (``tables[:, 0]``: the
    engine's allocator hands first blocks out of ``1 .. state_slots``);
    slot 0 is the pad rows' trash, as block 0 is.

    A homogeneous attention stack has the block pool alone, for every
    layer. ``window`` and ``full`` layers of the standard block in one
    stack (``cfg.standard_blocks``) have a block range for each ``full``
    layer and rings ``{"wk", "wv"}`` for the ``window`` layers, blocks
    ``[bs, K, D]`` as the homogeneous stack's (heads of 64 two to a row,
    ``[bs, K/2, 2 D]``: :func:`kv_lane_pack`), and state ``{"conv"}`` for
    its ``conv`` layers: the short convolution's last inputs. Where the
    block's attention is latent (``cfg.mla``) the stack has a range of
    latent blocks ``{"latent"}`` for each ``latent`` layer (the row
    below) and, for its ``kda`` layers, state ``{"kda"}``: the delta rule's
    matrix ``[heads, keys, values]`` in float32, and ``{"kda_conv"}``: the
    last inputs of its three convolutions (``hybrid.kda_state_shapes``).
    A stack of ``layer_kinds``
    with mixers of its own (``models/hybrid.py``) has the block
    pool for its ONE ``full`` layer (the ``cross`` layers read it), rings
    ``{"wk", "wv"}`` for its ``window`` layers and state for its ``mamba``
    layers: ``{"conv"}`` the convolution's last inputs, ``{"ssm"}`` the
    recurrence's matrix in float32 (a bfloat16 state was not tried on the
    chip). Its keys and values are stored as differential attention reads
    them, ``[.., K/2, 2 D]`` (``hybrid.paired_cache``), a block heads
    first: ``[K/2, bs, 2 D]``.

    MLA models (DeepSeek) pool the LATENTS instead of per-head K/V: one
    row per slot, ``c_kv [kv_lora_rank] ++ k_pe [qk_rope_head_dim]`` (the
    shared post-rope key) ++ zeros up to a lane multiple
    (:func:`latent_row_width`; reference ``ragged/kv_cache.py`` + the v2
    engine's DeepSeek containers). That small row (kvr+dr vs 2·K·D) is
    where paged KV pays off, and one row a position is what lets the
    kernel read each position once."""
    dt = dtype or cfg.compute_dtype
    L = cfg.num_layers
    if cfg.standard_blocks:
        # ordinary grouped-query blocks ``[bs, K, D]``: a block range for
        # EVERY full layer, a ring per sequence slot for every window layer
        # (``[layers, slots + 1, RB, bs, K, D]``: slots and ring length are
        # read off the array), nothing for a kind the stack lacks
        if state_slots < 1:
            raise ValueError("window and full layers in one stack keep "
                             "rings (and conv layers their state) per "
                             "sequence: state_slots >= 1 "
                             f"(got {state_slots})")
        pack = kv_lane_pack(cfg)
        kinds, block = cfg.layer_kinds, (block_size, cfg.kv_heads // pack,
                                         pack * cfg.head_dim)
        pool = {}
        if "full" in kinds:
            full = (kinds.count("full"), n_blocks) + block
            pool.update(k=jnp.zeros(full, dt), v=jnp.zeros(full, dt))
        if "window" in kinds:
            ring = (kinds.count("window"), state_slots + 1,
                    ring_blocks(cfg, block_size, max_run)) + block
            pool.update(wk=jnp.zeros(ring, dt), wv=jnp.zeros(ring, dt))
        if "latent" in kinds:
            pool["latent"] = jnp.zeros(
                (kinds.count("latent"), n_blocks, block_size,
                 latent_row_width(cfg)), dt)
        if "conv" in kinds:
            # the short convolution's last inputs
            pool["conv"] = _conv_store(
                kinds.count("conv"), state_slots,
                (cfg.conv_taps - 1, cfg.hidden_size), dt)
        if "kda" in kinds:
            rule, conv = HY.kda_state_shapes(cfg)
            rows = (kinds.count("kda"), state_slots + 1)
            pool["kda"] = jnp.zeros(rows + rule, jnp.float32)
            pool["kda_conv"] = _conv_store(kinds.count("kda"), state_slots,
                                           conv, dt)
        return pool
    if cfg.layer_kinds:
        kinds = cfg.layer_kinds
        if kinds.count("full") != 1 or state_slots < 1:
            raise ValueError(
                "a stack of layer_kinds has one `full` layer (the owner of "
                f"the block pool; got {kinds.count('full')}) and needs "
                f"state_slots >= 1 (got {state_slots})")
        # a block is [K/2, bs, 2 D]: heads first (10 paired heads cannot be
        # the second-minor dim of a block the kernel's copies slice)
        head = (cfg.kv_heads // HY.PAIR, block_size, HY.PAIR * cfg.head_dim)
        ring = (kinds.count("window"), (state_slots + 1)
                * ring_blocks(cfg, block_size, max_run)) + head
        state = (kinds.count("mamba"), state_slots + 1)
        return {"k": jnp.zeros((1, n_blocks) + head, dt),
                "v": jnp.zeros((1, n_blocks) + head, dt),
                "wk": jnp.zeros(ring, dt), "wv": jnp.zeros(ring, dt),
                "conv": _conv_store(kinds.count("mamba"), state_slots,
                                    (cfg.ssm_conv - 1, cfg.ssm_inner), dt),
                "ssm": jnp.zeros(state + (cfg.ssm_state, cfg.ssm_inner),
                                 jnp.float32)}
    if cfg.mla:
        return {"latent": jnp.zeros((L, n_blocks, block_size,
                                     latent_row_width(cfg)), dt)}
    shape = (L, n_blocks, block_size, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _conv_store(layers: int, state_slots: int, kept: tuple, dtype
                ) -> jax.Array:
    """The store of a convolution's last inputs: ``kept`` is what a
    sequence keeps a layer, ``(inputs, channels)`` (taps - 1 of them,
    oldest first); the store holds a ROW an input of a slot, inputs-major:
    ``[layers x inputs x (slots + 1), channels]``, layer ``l``'s input
    ``k`` the PLANE of ``slots + 1`` rows from ``(l x inputs + k) x (slots
    + 1)`` (:func:`_conv_rows`). The minor two dimensions of a state store
    fill their tiles: with the 2 or 3 inputs second-minor (``[.., slots +
    1, inputs, channels]``) a tile of 4 or 8 sublanes is a quarter or more
    padding, and XLA re-laid the whole store to the unpadded form and back
    wherever it pleased: on entry, on exit and as ``remat_compressed``
    pairs around the delta rule's calls, 4.6 ms of a 28.8 ms decode tick.
    Two dimensions and not ``[layers x inputs, slots + 1, channels]``: the
    device lays an array out by its shape, and puts 16 planes ahead of 273
    slots as the second-minor dimension, which is a copy of the whole
    store in and out of every tick again (PERF.md, PR 43)."""
    inputs, channels = kept
    return jnp.zeros((layers * inputs * (state_slots + 1), channels), dtype)


#: the stores of :func:`_conv_store`: rows already, they ride the layer
#: scans as they are
_CONV_STORES = ("conv", "kda_conv")


def _conv_rows(S1: int, slot: jax.Array, closes: jax.Array):
    """The two uses a layer makes of a store of :func:`_conv_store`:
    ``read(store, layer, inputs) -> inputs x [T, channels]``, the tick
    rows' slots' rows, and ``write(store, layer, new) -> store``, ``new``
    the inputs up to and including each row, each ``[T, channels]``: the
    state after a row that ``closes`` a run (its last row, of a real
    sequence) is its sequence's. A plane is written WHOLE: each slot's row
    is the row of ``new`` that closes the slot's run, or what the slot
    held where none does (so a pad row writes nothing) — a gather of
    ``S1`` rows, a select and the plane updated in place. A scatter of the
    tick's rows walks them one index after another, ~0.9 us each however
    wide: 18 of them cost a 2,048-row tick 29 ms where the planes take
    one (PERF.md, PR 43)."""
    # [S1, T]: the one row of the tick, if any, that closes each slot's run
    mine = closes[None, :] & (
        slot[None, :] == jnp.arange(S1, dtype=slot.dtype)[:, None])
    closing, closed = jnp.argmax(mine, axis=1), mine.any(axis=1)[:, None]

    def read(store, layer, inputs):
        return tuple(store[(layer * inputs + k) * S1 + slot]
                     for k in range(inputs))

    def write(store, layer, new):
        for k, x in enumerate(new):
            first = (layer * len(new) + k) * S1
            held = lax.dynamic_slice_in_dim(store, first, S1)
            store = lax.dynamic_update_slice_in_dim(
                store, jnp.where(closed, x[closing].astype(store.dtype),
                                 held), first, 0)
        return store

    return read, write


def paged_attention_reference(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                              tables: jax.Array, lengths: jax.Array,
                              alibi: Optional[jax.Array] = None,
                              scale: Optional[float] = None,
                              window: Optional[int] = None,
                              heads_first: bool = False) -> jax.Array:
    """Pure-XLA paged attention (the CPU/fallback path; the Pallas kernel in
    ``ops/pallas/paged_attention.py`` computes the same thing without
    materializing the gathered KV).

    q [T, N, D]; pools [NB, bs, K, D]; tables [T, MB]; lengths [T] (= pos+1).
    Token t attends to its sequence's first ``lengths[t]`` cache slots.
    ``alibi``: [N] slopes — cache slot c IS absolute position c, so the
    bias is ``slope · (c − (lengths−1))`` (matches ``cached_attention``).
    ``scale`` / ``window`` / ``heads_first`` (pools [NB, K, bs, D]): as
    the kernel's (``paged_attention``).
    """
    Tn, N, D = q.shape
    MB = tables.shape[1]
    kg = kpool[tables]                                   # [T, MB, bs, K, D]
    vg = vpool[tables]
    if heads_first:
        kg, vg = jnp.swapaxes(kg, 2, 3), jnp.swapaxes(vg, 2, 3)
    bs, K = kg.shape[2:4]
    kg = kg.reshape(Tn, MB * bs, K, D)
    vg = vg.reshape(Tn, MB * bs, K, D)
    if K != N:
        kg = jnp.repeat(kg, N // K, axis=2)
        vg = jnp.repeat(vg, N // K, axis=2)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(D))
    s = jnp.einsum("tnd,tcnd->tnc", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale       # [T, N, ctx]
    if alibi is not None:
        rel = (jnp.arange(MB * bs)[None, :]
               - (lengths[:, None] - 1)).astype(jnp.float32)  # [T, ctx]
        s = s + alibi.astype(jnp.float32)[None, :, None] * rel[:, None, :]
    mask = jnp.arange(MB * bs)[None, None, :] < lengths[:, None, None]
    if window is not None:
        mask &= jnp.arange(MB * bs)[None, None, :] \
            >= lengths[:, None, None] - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("tnc,tcnd->tnd", p, vg.astype(jnp.float32)).astype(q.dtype)


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


def latent_attention_reference(q_row: jax.Array, pool: jax.Array,
                               tables: jax.Array, lengths: jax.Array,
                               kvr: int, scale: float,
                               row_table: Optional[jax.Array] = None
                               ) -> jax.Array:
    """``ops/pallas/paged_attention.latent_paged_attention`` in plain jnp
    (the CPU path and the kernel's oracle): latent-space queries
    q_row [T, N, W] against pool rows [NBf, bs, W] -> the attended
    latents [T, N, kvr]. It gathers every row's whole table, so it is for
    short tables only. ``row_table``: ``tables`` is one a sequence slot and
    this each row's slot, as the kernel's."""
    if row_table is not None:
        tables = tables[row_table]
    Tn = q_row.shape[0]
    bs, MB = pool.shape[1], tables.shape[1]
    rows = pool[tables].reshape(Tn, MB * bs, pool.shape[2])
    s = jnp.einsum("tnw,tcw->tnc", q_row, rows).astype(jnp.float32) * scale
    mask = jnp.arange(MB * bs)[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1).astype(q_row.dtype)
    return jnp.einsum("tnc,tck->tnk", p, rows[..., :kvr])


def paged_mla_attention_reference(q: jax.Array, pool: jax.Array,
                                  tables: jax.Array, lengths: jax.Array,
                                  w_kv_b: jax.Array,
                                  cfg: T.TransformerConfig) -> jax.Array:
    """Weight-absorbed MLA attention over the paged LATENT pool (the
    DeepSeek decode trick of ``transformer._mla_absorbed_attention``, paged)
    around :func:`latent_attention_reference`.

    q [T, N, dn+dr] (post-rope); pool [NBf, bs, W] (rows ``c_kv ++ k_pe ++
    0``); tables [T, MB]; → [T, N, dv].
    """
    return _absorbed(q, w_kv_b, cfg, lambda q_row: latent_attention_reference(
        q_row, pool, tables, lengths, cfg.kv_lora_rank,
        mla_softmax_scale(cfg)))


def span_attention_reference(q: jax.Array, kpool: jax.Array,
                             vpool: jax.Array, tables: jax.Array,
                             lengths: jax.Array, window: Optional[int],
                             row_table: jax.Array,
                             scale: Optional[float] = None) -> jax.Array:
    """:func:`paged_attention_reference` given one table a sequence slot
    and each row's slot, as the kernel of a stack of window and full
    layers is (``paged_attention(row_table=)``)."""
    return paged_attention_reference(q, kpool, vpool, tables[row_table],
                                     lengths, window=window, scale=scale)


#: what ``forward_paged(attention_fn=)`` reads as "no kernel"
_REFERENCES = (None, paged_attention_reference, latent_attention_reference,
               span_attention_reference)


def tick_attention(cfg: T.TransformerConfig, use_kernel: bool
                   ) -> Tuple[Callable, int]:
    """The attention a tick of ``cfg`` runs over its pool, and the rows of
    that kernel's tile (0 where the plain-jnp reference runs): the ONE
    place that maps (model, kernels wanted) to a function. The Pallas
    kernel has no bias input yet, so ALiBi ticks take the reference
    (correct, rectangular-gather cost); a latent pool takes the kernel's
    latent instantiation, which is the kernel with one KV head.

    Dense pools: ``fn(q, kpool, vpool, tables, lengths[, alibi=])``;
    the latent pool: ``fn(q_row, pool, tables, lengths, kvr, scale)``;
    a stack of ``layer_kinds``: ``fn(q, kpool, vpool, tables, lengths,
    scale=, window=, heads_first=)`` over paired heads
    (``hybrid.paired_queries``), the dense kernel under the names ``window_paged_attention`` (a ring, ``window``
    positions) and ``shared_paged_attention`` (``window`` None: the one
    block pool, read by the ``full`` layer and every ``cross`` layer);
    ``window`` and ``full`` layers of the standard block
    (``cfg.standard_blocks``): ``fn(q, kpool, vpool, tables, lengths,
    window=, row_table=, scale=)`` with one table a sequence slot and each
    row's
    slot, the dense kernel under the names ``swa_attention`` (a
    ring) and ``global_attention`` (a full layer's own block range)."""
    if cfg.standard_blocks and not cfg.mla:
        if not use_kernel:
            return span_attention_reference, 0
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_attention, tile_rows)

        def attend(q, kpool, vpool, tables, lengths, window, row_table,
                   scale=None):
            # a chunk of the token budget against thousands of positions
            # is MXU-bound, unlike the homogeneous cells' shapes: the
            # products take the operands in the model's own type (bfloat16
            # as served, as the latent instantiation's) and accumulate in
            # float32
            return paged_attention(
                q, kpool, vpool, tables, lengths, window=window,
                mxu_dtype=q.dtype, row_table=row_table, scale=scale,
                name="global_attention" if window is None
                else "swa_attention")

        return attend, tile_rows(cfg.num_heads,
                                 kv_lane_pack(cfg) * cfg.head_dim)
    if cfg.layer_kinds and not cfg.standard_blocks:
        if not use_kernel:
            return paged_attention_reference, 0
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_attention, tile_rows)

        def attend(q, kpool, vpool, tables, lengths, scale, window,
                   heads_first):
            return paged_attention(
                q, kpool, vpool, tables, lengths, scale=scale, window=window,
                heads_first=heads_first,
                name="shared_paged_attention" if window is None
                else "window_paged_attention")

        return attend, tile_rows(cfg.num_heads, HY.PAIR * cfg.head_dim)
    if not use_kernel or cfg.pos_emb == "alibi":
        return (latent_attention_reference if cfg.mla
                else paged_attention_reference), 0
    from deepspeed_tpu.ops.pallas.paged_attention import (
        latent_paged_attention, paged_attention, tile_rows)

    if cfg.mla:
        return latent_paged_attention, tile_rows(cfg.num_heads,
                                                 cfg.kv_lora_rank)
    return paged_attention, tile_rows(cfg.num_heads, cfg.head_dim)


def tick_walks(cfg: T.TransformerConfig, pool: Dict[str, jax.Array]
               ) -> List[Tuple[int, Optional[int], int]]:
    """(layers, window, cache positions a fetch step) of each kind of call
    :func:`tick_attention`'s kernel makes in a tick of ``cfg`` over
    ``pool``: what ``ops.pallas.paged_attention.count_steps`` needs, beside
    a tick's lengths, to say how many fetch steps the tick walks. The step
    is the kernel's own rule of the operands' shapes (``_geometry``)."""
    from deepspeed_tpu.ops.pallas.paged_attention import _geometry

    def step(names, heads_first=False):
        # a call sees one block of each pool and the queries' heads
        blocks = [jax.ShapeDtypeStruct(
            (1,) + pool[n].shape[-2 if cfg.mla else -3:], pool[n].dtype)
            for n in names]
        width = blocks[0].shape[-1]
        q = jax.ShapeDtypeStruct((1, cfg.num_heads, width), blocks[0].dtype)
        _, bs, _, P = _geometry(
            q, blocks, cfg.kv_lora_rank if cfg.mla else width, heads_first)
        return bs * P

    kinds = cfg.layer_kinds or ()
    if cfg.mla:
        return [(kinds.count("latent") if kinds else cfg.num_layers, None,
                 step(("latent",)))]
    if cfg.standard_blocks:
        return [(kinds.count(kind), window, step(names))
                for kind, window, names in (
                    ("window", cfg.attn_window, ("wk", "wv")),
                    ("full", None, ("k", "v"))) if kind in kinds]
    if kinds:
        return [(kinds.count("window"), cfg.attn_window,
                 step(("wk", "wv"), True)),
                (kinds.count("full") + kinds.count("cross"), None,
                 step(("k", "v"), True))]
    return [(cfg.num_layers, None, step(("k", "v")))]


_EXPERT_LEAVES = ("w_up", "w_down", "w_gate")


def _tick_experts(h: jax.Array, lp: Dict[str, jax.Array],
                  cfg: T.TransformerConfig, valid: jax.Array,
                  stack: Dict[str, jax.Array], layer: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """A tick's expert layer on normed rows [T, H], DROPLESS
    (``moe.layer.dropless_moe_ffn``): (output, rows per expert over the
    ``valid`` rows). The experts' matrices are the whole layer
    ``stack``'s with the ``layer`` to use, or ``lp``'s where the stack is
    empty (quantised leaves, dequantised a layer at a time)."""
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
        layer=layer if stack else None,
        first_expert=cfg.moe_first_expert,
        route_norm_eps=cfg.moe_route_norm_eps)


class _Rows(NamedTuple):
    """A tick's rows as the skeleton derives them once for every layer."""
    positions: jax.Array    # [T]
    tables: jax.Array       # [T, MB] blocks within one layer's range
    block_idx: jax.Array    # [T] the block row t writes into
    offsets: jax.Array      # [T] its slot in that block
    lengths: jax.Array      # [T] cache slots row t attends to (= pos+1)


# The two cache kinds. Each takes (cfg, pool as stored, rows, the function
# ``tick_attention`` chose) and returns a layer's attention as
# ``layer(h, lp, flat, base) -> (attn [T, N*dv], flat)``: from the normed
# rows ``h [T, H]``, the layer's parameters and the flat pool carry
# (``forward_paged``), project, write the tick's rows into the layer's
# block range (which starts at block ``base``), attend, and return the
# attention output before ``wo`` with the new carry.

def _project_qkv(cfg: T.TransformerConfig, h: jax.Array,
                 lp: Dict[str, jax.Array], positions: jax.Array,
                 rope: Optional[Tuple[jax.Array, jax.Array]]
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A layer's queries, keys and values from its normed rows ``h [T, H]``:
    the projections with their biases, ``qk_norm``, and rotary at the rows'
    positions where ``rope`` (the cos and sin tables) is given."""
    dt = cfg.compute_dtype

    def proj(name, heads):
        out = h @ lp[f"w{name}"].astype(dt)
        if cfg.attn_bias_enabled:
            out = out + lp[f"b{name}"].astype(dt)
        return out.reshape(h.shape[0], heads, cfg.head_dim)

    q, k, v = (proj("q", cfg.num_heads), proj("k", cfg.kv_heads),
               proj("v", cfg.kv_heads))
    if cfg.qk_norm:
        q = T._head_rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        k = T._head_rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    if rope is not None:
        q = T.apply_rope_at(q[None], *rope, positions[None])[0]
        k = T.apply_rope_at(k[None], *rope, positions[None])[0]
    return q, k, v


def _dense_cache(cfg: T.TransformerConfig, pool: Dict[str, jax.Array],
                 rows: _Rows, attend: Callable) -> Callable:
    """Per-head K/V pools ``{"k", "v"}`` [L, NB, bs, K, D]: q/k/v
    projections with their biases, ``qk_norm``, rotary at the rows'
    positions; ALiBi models (BLOOM/Falcon) bias the paged scores by head
    slope × relative position."""
    dt = cfg.compute_dtype
    Tn = rows.positions.shape[0]
    NB, bs = pool["k"].shape[1:3]
    cos_t = sin_t = None
    if cfg.pos_emb == "rope":
        cos_t, sin_t = T.rope_table(NB * bs, cfg.rope_dim, cfg.rope_theta,
                                    cfg.rope_scaling_dict)
    bias = {}
    if cfg.pos_emb == "alibi":
        bias["alibi"] = T.alibi_slopes(cfg.num_heads) * cfg.alibi_bias_scale

    def layer(h, lp, flat, base):
        q, k, v = _project_qkv(cfg, h, lp, rows.positions,
                               (cos_t, sin_t) if cfg.pos_emb == "rope"
                               else None)
        # blocked KV write (reference ragged_ops KV-copy kernels): token t →
        # pool[base + block_idx[t], offsets[t]]. Pad tokens hit this layer's
        # trash block (block 0 of its range — never allocated).
        pk, pv = flat["k"], flat["v"]
        pk = pk.at[base + rows.block_idx, rows.offsets].set(
            k.astype(pk.dtype), mode="drop")
        pv = pv.at[base + rows.block_idx, rows.offsets].set(
            v.astype(pv.dtype), mode="drop")
        attn = attend(q, pk, pv, rows.tables + base, rows.lengths, **bias)
        return (attn.reshape(Tn, cfg.num_heads * cfg.head_dim),
                {"k": pk, "v": pv})

    return layer


def _latent_cache(cfg: T.TransformerConfig, pool: Dict[str, jax.Array],
                  rows: _Rows, attend: Callable,
                  by_slot: Optional[Tuple[jax.Array, jax.Array]] = None
                  ) -> Callable:
    """The MLA (DeepSeek) pool ``{"latent"}`` [L, NB, bs, W]: a row's
    latent (``c_kv ++ k_pe``, padded to the lanes) is what is written,
    and attention is weight-absorbed (:func:`_absorbed`; same math as the
    v1 engine's latent-cache decode). ``by_slot`` (one table a sequence
    slot, each row's slot), where the stack has slots: the kernel is then
    given those (:func:`_span_cache` says why) and not a table a row."""
    tables, which = (rows.tables, {}) if by_slot is None \
        else (by_slot[0], {"row_table": by_slot[1]})
    lat = pool["latent"]
    Tn = rows.positions.shape[0]
    NB, bs, W = lat.shape[1:]
    if cfg.pos_emb == "rope":
        cos_t, sin_t = T.rope_table(NB * bs, cfg.qk_rope_head_dim,
                                    cfg.rope_theta, cfg.rope_scaling_dict)

    def rope_fn(v):                                   # v [T, 1, n, dr]
        if cfg.pos_emb != "rope":
            return v           # the model rotates nothing (``mla_use_nope``)
        return T.apply_rope_at(v, cos_t, sin_t, rows.positions[:, None])

    row_pad = jnp.zeros(
        (Tn, W - cfg.kv_lora_rank - cfg.qk_rope_head_dim), lat.dtype)

    def layer(h, lp, flat, base):
        plat = flat["latent"]
        hB = h[:, None, :]                            # [T, 1, H]
        q = T._mla_q(hB, lp, cfg, rope_fn)[:, 0]      # [T, N, dn+dr]
        c_kv, k_pe = T._mla_latents(hB, lp, cfg, rope_fn)
        row = jnp.concatenate(
            [c_kv[:, 0].astype(plat.dtype),
             k_pe[:, 0, 0].astype(plat.dtype), row_pad], axis=-1)
        plat = plat.at[base + rows.block_idx, rows.offsets].set(
            row, mode="drop")
        attn = _absorbed(q, lp["wkv_b"], cfg, lambda q_row: attend(
            q_row, plat, tables + base, rows.lengths,
            cfg.kv_lora_rank, mla_softmax_scale(cfg), **which))
        return (attn.reshape(Tn, cfg.num_heads * cfg.v_head_dim),
                {"latent": plat})

    return layer


def pool_block(pool: Dict[str, jax.Array]) -> int:
    """Positions of a block of a standard-block pool: ``[bs, K, D]``, or a
    latent block ``[bs, W]``."""
    if "latent" in pool:
        return pool["latent"].shape[-2]
    return pool["k" if "k" in pool else "wk"].shape[-3]


def _span_cache(cfg: T.TransformerConfig, pool: Dict[str, jax.Array],
                rows: _Rows, attend: Callable) -> Callable:
    """The caches of ``window``, ``full``, ``latent``, ``conv`` and ``kda``
    layers of the standard block (``cfg.standard_blocks``;
    ``init_paged_kv``: block ranges ``k, v`` or ``latent``, rings ``wk,
    wv``, state ``conv`` or ``kda, kda_conv``): a layer's
    mixer as ``layer(kind, h, lp, flat, nth) -> (mixed [T, .] before
    ``wo``, flat)``, ``nth`` the layer's index among the layers of its KIND
    (which ring, which block range, which state rows). A ``conv`` layer
    reads its rows' runs' state from its sequence slots' rows and writes
    the state after each run's last row (``hybrid.short_conv``); a ``kda``
    layer likewise, its convolutions' inputs so and the rule's matrix
    inside ``hybrid.delta_rule`` (a run's is read at its first row and
    written after its last, in place); a ``latent`` layer is
    :func:`_latent_cache`'s over its own range of latent blocks.
    Projections as :func:`_dense_cache`'s (``qk_norm``; rotary at
    the rows' positions, on ``full`` layers only where the config says
    so), then the elementwise output gate where the model has one.

    A ``full`` layer writes and walks its own block range through the
    rows' tables. A ``window`` layer's cache is a ring of its sequence's
    slot (the table's first block): a tick's rows are written before any
    attends, so a row walks the ring through a table of its own, column
    ``c`` naming the slot's block ``c % RB``, which holds positions
    ``c*bs ..`` if any of them is inside the row's window."""
    dt = cfg.compute_dtype
    Tn, MB = rows.tables.shape
    cos_t = sin_t = None
    if cfg.pos_emb == "rope":
        # a position lies within the tables' reach
        cos_t, sin_t = T.rope_table(MB * pool_block(pool), cfg.rope_dim,
                                    cfg.rope_theta, cfg.rope_scaling_dict)
    # the kernel is given one table a sequence SLOT and each row's slot (a
    # budget of thousands of rows, each with a table of hundreds of blocks
    # of its own, has no room in scalar memory); a stack without window
    # layers has no slots: a table a row
    slot, by_slot = jnp.arange(Tn, dtype=jnp.int32), rows.tables
    per_slot = [n for n in ("wk", "conv", "kda") if n in pool]
    if per_slot:
        # slots + 1: a convolution store's rows are (layer, input, slot)
        S1 = pool["conv"].shape[0] // (cfg.layer_kinds.count("conv") * (
            cfg.conv_taps - 1)) if per_slot[0] == "conv" \
            else pool[per_slot[0]].shape[1]
        slot = rows.tables[:, 0]
        by_slot = jnp.zeros((S1, MB), jnp.int32).at[slot].set(rows.tables)
    if "wk" in pool:
        RB, bs = pool["wk"].shape[2:4]
        ring_by_slot = (jnp.arange(S1, dtype=jnp.int32) * RB)[:, None] + (
            jnp.arange(MB, dtype=jnp.int32) % RB)[None, :]
        ring_block = slot * RB + (rows.positions // bs) % RB
    NB = pool["k"].shape[1] if "k" in pool else 0
    pack = kv_lane_pack(cfg)
    if "conv" in pool or "kda" in pool:
        runs = HY.runs_of(slot, rows.positions)
        read_conv, write_conv = _conv_rows(S1, slot,
                                           runs.last & (slot > 0))
    if "latent" in pool:
        latent = _latent_cache(cfg, pool, rows, attend,
                               (by_slot, slot) if per_slot else None)

    def layer(kind, h, lp, flat, nth):
        if kind == "latent":
            attn, new = latent(h, lp, flat, nth * pool["latent"].shape[1])
            return attn, {**flat, **new}
        if kind == "kda":
            inputs, conv = HY.kda_inputs(
                h, lp, cfg, runs,
                read_conv(flat["kda_conv"], nth, cfg.kda_conv - 1))
            # a pad row's sequence is none: row 0 of the store
            o, state = HY.delta_rule(
                *inputs, runs, flat["kda"],
                jnp.where(slot > 0, nth * S1 + slot, 0),
                use_kernel=attend not in _REFERENCES)
            return HY.kda_output(o, h, lp, cfg), {
                **flat, "kda": state,
                "kda_conv": write_conv(flat["kda_conv"], nth, conv)}
        if kind == "conv":
            mixed, conv = HY.short_conv(
                h, lp, runs, read_conv(flat["conv"], nth, cfg.conv_taps - 1))
            return mixed, {**flat,
                           "conv": write_conv(flat["conv"], nth, conv)}
        q, k, v = _project_qkv(
            cfg, h, lp, rows.positions,
            (cos_t, sin_t) if cfg.pos_emb == "rope" and (
                kind == "window" or cfg.full_layers_rope) else None)
        if kind == "window":
            names, base = ("wk", "wv"), nth * (S1 * RB)
            at, tables, window = base + ring_block, ring_by_slot + base, \
                cfg.attn_window
        else:
            names, base = ("k", "v"), nth * NB
            at, tables, window = base + rows.block_idx, by_slot + base, None
        new, scale, own = dict(flat), None, None
        if pack > 1:
            # heads of 64 lie two to a pool row (``kv_lane_pack``); the
            # scores' factor stays the unpacked head's
            k, v = (x.reshape(Tn, cfg.kv_heads // pack, -1) for x in (k, v))
            q, own = _lane_packed(q, cfg.kv_heads)
            scale = cfg.head_dim ** -0.5
        for name, x in zip(names, (k, v)):
            new[name] = flat[name].at[at, rows.offsets].set(
                x.astype(flat[name].dtype), mode="drop")
        # the scope a device trace tells the two kinds' attention by
        with jax.named_scope("swa" if kind == "window" else "global"):
            attn = attend(q, new[names[0]], new[names[1]], tables,
                          rows.lengths, window=window, row_table=slot,
                          scale=scale)
        if own is not None:
            attn = own(attn)
        attn = attn.reshape(Tn, cfg.num_heads * cfg.head_dim)
        if cfg.attn_gate:
            attn = attn * jax.nn.sigmoid(h @ lp["wg"].astype(dt))
        return attn, new

    return layer


def _kinds_cache(cfg: T.TransformerConfig, pool: Dict[str, jax.Array],
                 rows: _Rows, attend: Callable) -> Callable:
    """The caches of a stack of ``layer_kinds`` (``init_paged_kv``): the
    mixer of one layer as ``layer(kind, h, lp, flat, step, index, memory)
    -> (mixed [T, .] before ``wo``, flat, memory)``. ``step`` counts the
    stack's PAIRS of layers, which is the index of a pair's ``mamba``
    layer among the state's layers and of its ``window`` layer among the
    rings' (every pair up to the ``full`` layer's has one of each);
    ``index`` is the layer's own. ``memory`` [T, inner] is the last
    ``mamba`` layer's scan output, which the ``gmu`` layers gate: an
    activation of the tick, not a cache.

    A row's slot is its table's first block. A tick's rows are written
    before any attends, so a ``window`` row walks its ring through a table
    of its own: column ``c`` names the slot's block ``c % RB``, which holds
    positions ``c*bs ..`` if any of them is inside the row's window."""
    dt = cfg.compute_dtype
    Tn, MB = rows.tables.shape
    N, K, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    bs = pool["k"].shape[3]
    S1 = pool["ssm"].shape[1]
    ring_rows = pool["wk"].shape[1]
    RB = ring_rows // S1
    slot = rows.tables[:, 0]
    runs = HY.runs_of(slot, rows.positions)
    read_conv, write_conv = _conv_rows(S1, slot, runs.last & (slot > 0))
    ring_tables = slot[:, None] * RB + (jnp.arange(MB, dtype=jnp.int32)
                                        % RB)[None, :]
    ring_block = slot * RB + (rows.positions // bs) % RB
    scale = D ** -0.5                       # of the unpaired heads

    def heads(h, w, n):
        return (h @ w.astype(dt)).reshape(Tn, n, D)

    def write(flat, names, at, lp, h):
        new = dict(flat)
        for name, w in zip(names, ("wk", "wv")):
            # (block, head, slot) index every written row, so that the
            # scatter's one window dim is the array's minor one
            new[name] = flat[name].at[
                at[:, None], jnp.arange(K // HY.PAIR)[None, :],
                rows.offsets[:, None]].set(
                HY.paired_cache(heads(h, lp[w], K)).astype(flat[name].dtype),
                mode="drop")
        return new

    def layer(kind, h, lp, flat, step, index, memory):
        if kind == "mamba":
            at = step * S1 + slot
            out, memory, conv, ssm = HY.mamba(
                h, lp, cfg, runs,
                read_conv(flat["conv"], step, cfg.ssm_conv - 1),
                flat["ssm"][at])
            # the state after a run's last row is its sequence's; the
            # other rows' index lies past the array and is dropped
            put = jnp.where(runs.last, at, flat["ssm"].shape[0])
            flat = {**flat,
                    "conv": write_conv(flat["conv"], step, conv),
                    "ssm": flat["ssm"].at[put].set(ssm, mode="drop")}
            return out, flat, memory
        if kind == "gmu":
            return HY.gmu(h, lp, memory), flat, memory
        q = HY.paired_queries(heads(h, lp["wq"], N))
        if kind == "window":
            base = step * ring_rows
            flat = write(flat, ("wk", "wv"), base + ring_block, lp, h)
            o = attend(q, flat["wk"], flat["wv"], ring_tables + base,
                       rows.lengths, scale=scale, window=cfg.attn_window,
                       heads_first=True)
        else:
            if kind == "full":
                flat = write(flat, ("k", "v"), rows.block_idx, lp, h)
            o = attend(q, flat["k"], flat["v"], rows.tables, rows.lengths,
                       scale=scale, window=None, heads_first=True)
        return (HY.differential_merge(o, lp, index, cfg.norm_eps).astype(dt),
                flat, memory)

    return layer


#: the scope a kind's mixer runs under (``attn`` also holds ``ln1``, ``wo``)
_KIND_SCOPES = {"mamba": "ssm", "gmu": "gmu", "conv": "conv", "kda": "kda"}


def forward_paged(params: PyTree, tokens: jax.Array, positions: jax.Array,
                  tables: jax.Array, pool: Dict[str, jax.Array],
                  cfg: T.TransformerConfig,
                  attention_fn: Optional[Callable] = None,
                  with_stats: bool = False,
                  head_rows: Optional[jax.Array] = None):
    """One SplitFuse tick over a flat token batch.

    tokens [T] int32, positions [T] int32, tables [T, MB] int32 (rows shared
    by tokens of the same sequence). Returns (logits [T, vocab] fp32,
    updated pool). Parity: the reference's model-implementation forward over
    a RaggedBatchWrapper (``inference/v2/model_implementations``).

    ``head_rows`` [S] int32: the head runs for those rows of the tick only
    (gathered from the last hidden state, before the final norm) and the
    logits are [S, vocab], row ``i`` those of tick row ``head_rows[i]``.

    ``with_stats`` adds a third result, ``{"expert_rows": [expert layers,
    E] int32}`` (rows each expert got from the tick's real rows; ``{}`` for
    a model without experts).
    """
    x, new_pool, stats = forward_hidden(
        params, tokens, positions, tables, pool, cfg, attention_fn)
    logits = head_logits(
        params, x if head_rows is None else x[head_rows], cfg)
    if with_stats:
        return logits, new_pool, stats
    return logits, new_pool


def head_logits(params: PyTree, x: jax.Array,
                cfg: T.TransformerConfig) -> jax.Array:
    """The head over rows of the last hidden state: final norm and the
    vocabulary matmul, [rows, vocab] fp32."""
    with jax.named_scope("lm_head"):
        x = T._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        head = T._lm_head_of(params, cfg)
        logits = T.head_matmul(x, head.astype(x.dtype))
        if cfg.lm_head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
    return logits


def forward_hidden(params: PyTree, tokens: jax.Array, positions: jax.Array,
                   tables: jax.Array, pool: Dict[str, jax.Array],
                   cfg: T.TransformerConfig,
                   attention_fn: Optional[Callable] = None):
    """:func:`forward_paged` up to the head: (the last hidden state [T, H],
    updated pool, stats).

    One skeleton for every model: embed, the rows' blocks and lengths, one
    scan per segment of ``cfg.segments`` (leading dense layers, then the
    stack; the pool's layers in the same order), the residual form, FFN or
    experts, the head. What a layer's attention projects, writes into the
    pool and attends to is the cache kind's (:func:`_dense_cache`,
    :func:`_latent_cache`), picked once from ``cfg.mla``. A segment of
    ``layer_kinds`` steps a period of layers at a time, each with the mixer
    of its kind over the cache of its kind (:func:`_kinds_cache`).

    ``attention_fn`` says whether kernels are wanted: ``None`` or a
    reference means no, anything else yes; which function then runs is
    ``cfg``'s to say, not the caller's (:func:`tick_attention`).

    Expert layers run dropless (:func:`_tick_experts`); ``stats`` is
    ``{"expert_rows": ...}`` for a model with experts, else ``{}``.
    """
    from deepspeed_tpu.ops.quantization import dequant_params

    attend, _ = tick_attention(cfg, attention_fn not in _REFERENCES)
    dt = cfg.compute_dtype
    if cfg.standard_blocks:
        NB, bs = 0, pool_block(pool)
    else:
        NB, bs = pool["latent" if cfg.mla else "k"].shape[1:3]
    if cfg.layer_kinds and not cfg.standard_blocks:
        bs = pool["k"].shape[3]             # its blocks are [K/2, bs, 2 D]

    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]             # [T, H]
        if cfg.emb_multiplier != 1.0:
            x = x * jnp.asarray(cfg.emb_multiplier, dt)
        if cfg.pos_emb == "learned":
            x = x + params["pos_emb"].astype(dt)[positions]
        if cfg.emb_norm:
            x = T._norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)

    rows = _Rows(positions, tables,
                 block_idx=jnp.take_along_axis(
                     tables, (positions // bs)[:, None], axis=1)[:, 0],
                 offsets=positions % bs, lengths=positions + 1)
    valid = tables[:, 0] > 0     # a pad row's table is all trash block
    attention = (_span_cache if cfg.standard_blocks else _kinds_cache
                 if cfg.layer_kinds else _latent_cache
                 if cfg.mla else _dense_cache)(cfg, pool, rows, attend)

    def make_body(seg: T.TransformerConfig, first: int, stack):
        def body(carry, lp):
            x, flat, li = carry
            lp = dequant_params(lp, dt)   # weight-only quant: per-layer dequant
            with jax.named_scope("attn"):
                h = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
                attn, flat = attention(h, lp, flat, li * NB)
                attn_out = attn @ lp["wo"].astype(dt)
                if seg.use_bias:
                    attn_out = attn_out + lp["bo"].astype(dt)
            # ``mlp`` is a dense FFN's scope; an expert layer's operations
            # carry ``router`` / ``experts`` / ``shared_experts``
            with contextlib.nullcontext() if seg.n_experts \
                    else jax.named_scope("mlp"):
                # the parallel residual norms the block's input (or shares
                # ``ln1``'s output), the sequential one what attention left
                resid = x + attn_out
                if not seg.parallel_block:
                    h2 = T._norm(resid, lp["ln2"], seg.norm, seg.norm_eps)
                elif seg.shared_parallel_norm:
                    h2 = h
                else:
                    h2 = T._norm(x, lp["ln2"], seg.norm, seg.norm_eps)
                if seg.n_experts:
                    down, n_rows = _tick_experts(h2, lp, seg, valid, stack,
                                                 li - first)
                else:
                    down, n_rows = T._ffn(h2, lp, seg)[0], None
                x = resid + down
            return (x, flat, li + 1), n_rows

        return body

    def make_period_body(seg: T.TransformerConfig):
        """A step of a segment of ``layer_kinds``: its period's layers,
        each ``x += wo(Mixer(ln1 x)); x += FFN(ln2 x)``."""
        def body(carry, lps):
            x, flat, step, memory = carry
            for i, kind in enumerate(seg.period):
                lp = dequant_params(lps[kind], dt)
                with jax.named_scope(_KIND_SCOPES.get(kind, "attn")):
                    h = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
                    # ``step`` counts pairs from the stack's first layer
                    mixed, flat, memory = attention(
                        kind, h, lp, flat, step, 2 * step + i, memory)
                    x = x + mixed @ lp["wo"].astype(dt)
                with jax.named_scope("mlp"):
                    h2 = T._norm(x, lp["ln2"], seg.norm, seg.norm_eps)
                    x = x + T._ffn(h2, lp, seg)[0]
            return (x, flat, step + 1, memory), None

        return body

    def blocks_body_of(seg: T.TransformerConfig, first: int, stack,
                       before: Dict[str, int]):
        """A step of a segment of standard blocks under ``layer_kinds``
        (``T.scan_periods``): a period's layers, each the sandwich or the
        plain sequential block around the attention of its kind and a
        dense FFN or the experts. ``before``: the layers of each kind
        ahead of the segment (a layer's ring or block range is its index
        among its kind's)."""
        def body_of(period, run_first):
            per = {k: period.count(k) for k in set(period)}
            ahead = {k: before.get(k, 0)
                     + seg.layer_kinds[:run_first].count(k) for k in per}

            def body(carry, lps):
                x, flat, li = carry
                step = (li - first - run_first) // len(period)
                n_rows = []
                for i, kind in enumerate(period):
                    lp = dequant_params(T.period_layer(lps, period, i), dt)
                    with jax.named_scope(_KIND_SCOPES.get(kind, "attn")):
                        h = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
                        attn, flat = attention(
                            kind, h, lp, flat, ahead[kind]
                            + step * per[kind] + period[:i].count(kind))
                        attn_out = attn @ lp["wo"].astype(dt)
                        if seg.post_norms:
                            attn_out = T._norm(attn_out, lp["ln1_post"],
                                               seg.norm, seg.norm_eps)
                        x = x + attn_out
                    with contextlib.nullcontext() if seg.n_experts \
                            else jax.named_scope("mlp"):
                        h2 = T._norm(x, lp["ln2"], seg.norm, seg.norm_eps)
                        if seg.n_experts:
                            down, rows_e = _tick_experts(
                                h2, lp, seg, valid, stack, li + i - first)
                            n_rows.append(rows_e)
                        else:
                            down = T._ffn(h2, lp, seg)[0]
                        if seg.post_norms:
                            down = T._norm(down, lp["ln2_post"], seg.norm,
                                           seg.norm_eps)
                        x = x + down
                return (x, flat, li + len(period)), \
                    jnp.stack(n_rows) if n_rows else None

            return body

        return body_of

    # The pool rides the layer scans as a FLAT [L*NB, bs, ...] carry that is
    # scattered in place (layer l owns block range [l*NB, (l+1)*NB)); the
    # attention kernel gathers through layer-offset tables, reading only the
    # listed blocks. Threading per-layer slices as scan xs→ys (the naive
    # layout) re-stacks the ENTIRE pool every call — measured 25 ms/tick at
    # 512 blocks inside a decode scan, linear in pool size — where the
    # in-place carry touches only the written rows.
    # (a ring of standard blocks is [layers, slots, RB, bs, K, D]: its rows
    # are blocks too; the stores of ``_CONV_STORES`` are rows already)
    carry = (x, {k: v if k in _CONV_STORES else v.reshape((-1,) + (
        v.shape[-3:] if cfg.standard_blocks and k in ("wk", "wv")
        else v.shape[2:])) for k, v in pool.items()}, jnp.int32(0))
    if cfg.layer_kinds and not cfg.standard_blocks:
        carry += (jnp.zeros((x.shape[0], cfg.ssm_inner), dt),)
    stats = {}
    first = 0
    before: Dict[str, int] = {}
    for key, seg in cfg.segments:
        if seg.period:
            carry, _ = lax.scan(make_period_body(seg), carry, params[key])
            continue
        # the experts' matrices stay out of the scan's sliced operands: the
        # grouped matmul takes the stack whole (``moe.layer.grouped_dot``;
        # a slice is a copy of a layer's experts before each matmul);
        # quantised leaves ({"q", "scale", ...}) are dequantised a layer at
        # a time and stay in
        stack = {k: v for k, v in params[key].items() if seg.n_experts
                 and k in _EXPERT_LEAVES and hasattr(v, "ndim")}
        xs = {k: v for k, v in params[key].items() if k not in stack}
        if cfg.standard_blocks:
            carry, n_rows = T.scan_periods(
                blocks_body_of(seg, first, stack, dict(before)), carry, xs,
                seg.layer_kinds)
            first += seg.num_layers
            for kind in seg.layer_kinds:
                before[kind] = before.get(kind, 0) + 1
            if seg.n_experts:
                # [steps, period, E] a run -> [expert layers, E]
                stats["expert_rows"] = jnp.concatenate(
                    [r.reshape((-1,) + r.shape[2:]) for r in n_rows])
            continue
        carry, n_rows = lax.scan(make_body(seg, first, stack), carry, xs)
        first += seg.num_layers
        if n_rows is not None:
            stats["expert_rows"] = n_rows
    x, flat = carry[:2]
    return x, {k: flat[k].reshape(v.shape) for k, v in pool.items()}, stats
