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
import functools
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


def index_row_width(cfg: T.TransformerConfig) -> int:
    """Columns of a row of a ``sparse`` layer's store of index keys: the
    indexer's ``index_head_dim`` rounded up to the TPU's 128 lanes, zeros
    beyond. A row is PADDED and two positions do not share one: a row of
    64 bfloat16 values is half a lane width, which the array occupies in
    HBM anyway while its minor dimension is 64, and a store ``[bs / 2,
    128]`` with two positions a row would make a tick's write a
    read-modify-write of rows that two of its own rows share (positions p
    and p + 1 of one chunk). What the padding costs: 256 B a position a
    layer where 128 B are values (at the published widths 12.5 % on a
    position's 2,048 B of keys and values instead of 6.25 %; 340 MB of the
    new cell's 6.1 GB pool) and twice the bytes the indexer's walk
    fetches; no product is wider for it (a 128-lane MXU contracts 64
    columns in the passes it contracts 128)."""
    return -(-cfg.index_head_dim // 128) * 128


def ring_blocks(cfg: T.TransformerConfig, block_size: int,
                max_run: int) -> int:
    """Blocks of a window layer's ring, per sequence: a tick writes a
    sequence's rows before any of them attends, so the ring holds the
    window and the longest run of rows one sequence can have in a tick."""
    return -(-(cfg.attn_window + max_run) // block_size)


def kv_lane_pack(cfg: T.TransformerConfig) -> int:
    """KV heads that lie side by side in one row of a pool of the standard
    block under kinds (:func:`cache_kinds` says which stacks):
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
    return max((kind.attend.pack for kind in cache_kinds(cfg).values()
                if kind.attend is not None), default=1)


def _lane_packed(q: jax.Array, kv_heads: int) -> Tuple[jax.Array, Callable]:
    """Queries q [T, N, D] against KV heads stored two to a row
    (:func:`kv_lane_pack`): (the queries [T, N, 2 D], zero in the half of
    the other head of the pair; a function that takes each head's own half
    of the attended values [T, N, 2 D] -> [T, N, D])."""
    N, D = q.shape[1:]
    odd = (jnp.arange(N) // (N // kv_heads)) % 2 == 1
    return HY.paired_queries(q, odd), lambda o: jnp.where(
        odd[None, :, None], o[..., D:], o[..., :D])


# --------------------------------------------------------------------------- #
# the table of cache kinds
# --------------------------------------------------------------------------- #

#: the classes of a pool's stores: what a store's rows are, and so where
#: its leading dimensions come from (:func:`init_paged_kv`)
BLOCKS, RING, SLOT, CONV = "blocks", "ring", "slot", "conv"


class Store(NamedTuple):
    """One array of a pool, as the kind of layer that owns it states it.

    * ``BLOCKS`` ``[layers, n_blocks, *unit]``: grows with a sequence a
      block at a time through its block table; block 0 is the trash block
      pad rows write into;
    * ``RING`` ``[layers, slots + 1, RB, *unit]``: ``RB`` blocks
      (:func:`ring_blocks`) a sequence slot, position ``p`` in block
      ``(p // bs) % RB`` of its slot's, whatever the sequence's length
      (``flat``: slots and ring blocks are ONE dimension, ``[layers,
      (slots + 1) * RB, *unit]``);
    * ``SLOT`` ``[layers, slots + 1, *unit]``: one row a sequence slot;
    * ``CONV`` ``[layers x inputs x (slots + 1), channels]``: a
      convolution's last inputs, a row an input of a slot
      (:func:`_conv_store`); ``unit`` is ``(inputs, channels)``.

    ``unit(block_size)``: the shape of one block, or of a slot's row;
    ``positions``: the axis of a block that counts positions; ``holds``:
    what a slot's rows are, for the engine's gauge of bytes a slot."""
    name: str
    cls: str
    unit: Callable[[int], tuple]
    dtype: Any = None                   # None: the pool's own
    holds: str = ""
    positions: int = -3
    flat: bool = False


class Attend(NamedTuple):
    """The attention call a kind of layer makes: what
    ``ops.pallas.paged_attention``'s kernel is told beside its operands."""
    stores: Tuple[str, ...]             # the stores it walks
    name: str                           # the Mosaic call's name
    window: Optional[int]
    width: int                          # the kernel's value columns
    heads_first: bool = False           # a block is [K, bs, D]
    # the products take the operands in the queries' own type (else the
    # kernel's default, float32): a chunk of the token budget against
    # thousands of positions is MXU-bound, unlike the homogeneous cells'
    # shapes
    own_dtype: bool = False
    # one table a sequence SLOT and each row's slot (a budget of thousands
    # of rows, each with a table of hundreds of blocks of its own, has no
    # room in scalar memory), else a table a row
    by_slot: bool = False
    scope: Optional[str] = None         # the scope a trace tells the call by
    rope: int = 0                       # columns rotated at the rows' positions
    pack: int = 1                       # KV heads a pool row (kv_lane_pack)


class CacheKind(NamedTuple):
    """What a kind of layer keeps of its sequences between ticks and how
    its mixer is built: one entry of :func:`cache_kinds`."""
    layers: int                         # layers of the kind in the stack
    scope: str                          # the scope its mixer runs under
    stores: Tuple[Store, ...]           # what its layers own
    attend: Optional[Attend]
    mixer: Callable                     # builds its mixer: see below
    # what its layers hand on to later layers of a tick: name -> columns
    acts: Tuple[Tuple[str, int], ...] = ()
    # ``(decode rows, chunk starts, rows, bucket, the rows' lengths) ->
    # span attributes``: what a tick of that shape does to the kind's
    # state, for the engine's span
    span: Optional[Callable] = None
    # ``(rows of a tick, positions a table reaches) -> bytes``: what a
    # layer of the kind materialises inside a tick beside the pool, where
    # that is too large to go unreckoned (the engine's check of its memory)
    tick_bytes: Optional[Callable] = None


def cache_kinds(cfg: T.TransformerConfig) -> Dict[str, CacheKind]:
    """The ONE table of what a model keeps of its sequences between ticks:
    an entry for every kind of layer the stack has, in the order the
    kinds' calls are counted (:func:`tick_walks`). A homogeneous stack is
    its ``full`` (or ``latent``) entry ``num_layers`` times; a LOOPED one
    (``cfg.loop_passes``) ``loop_passes x num_layers`` times: an entry
    counts CACHE layers, and pass ``t`` of layer ``l`` owns cache layer
    ``t * num_layers + l`` (a later position's pass ``t`` attends to this
    position's pass-``t`` keys and values). The pool
    (:func:`init_paged_kv`), the tick's carry, the engine's bytes and
    gauges, the mixers and the kernel calls all come from here, and this is
    the one place that reads which family ``cfg`` is: grouped-query,
    latent (``cfg.mla``) or differential attention; the standard block
    (``cfg.standard_blocks``: a kind says what a layer sees and where its
    cache lives) or mixers that are whole layers of their own
    (``models/hybrid.py``).

    MLA models (DeepSeek) pool the LATENTS instead of per-head K/V: one
    row per position, ``c_kv [kv_lora_rank] ++ k_pe [qk_rope_head_dim]``
    (the shared post-rope key) ++ zeros up to a lane multiple
    (:func:`latent_row_width`; reference ``ragged/kv_cache.py`` + the v2
    engine's DeepSeek containers). That small row (kvr+dr vs 2·K·D) is
    where paged KV pays off, and one row a position is what lets the
    kernel read each position once. The delta rule's matrix and the
    recurrence's are float32 (a bfloat16 state was not tried on the chip).
    The state-space family's ONE ``full`` layer owns the block pool its
    ``cross`` layers read; its keys and values are stored as differential
    attention reads them, ``[.., K/2, 2 D]`` (``hybrid.paired_cache``), a
    block heads first, ``[K/2, bs, 2 D]``: 10 paired heads cannot be the
    second-minor dim of a block the kernel's copies slice."""
    kinds, of_kinds = cfg.layer_kinds, cfg.standard_blocks
    n = kinds.count
    T._check_loop(cfg)
    if any(own is not None for _, own in cfg.kind_rope):
        raise NotImplementedError(
            "a rotary table of its own for a layer kind (kind_rope) is "
            "trained and run whole by forward(); a paged tick rotates every "
            "layer by the model's one table")
    if kinds and not of_kinds:
        if n("full") != 1:
            raise ValueError(
                "a stack of layer_kinds has one `full` layer (the owner of "
                f"the block pool; got {n('full')})")

        def head(bs):
            return (cfg.kv_heads // HY.PAIR, bs, HY.PAIR * cfg.head_dim)

        def paired(stores, window, name):
            return Attend(stores, name, window, HY.PAIR * cfg.head_dim,
                          heads_first=True)

        shared = paired(("k", "v"), None, "shared_paged_attention")
        table = {
            "mamba": CacheKind(
                n("mamba"), "ssm",
                (Store("conv", CONV, lambda bs: (cfg.ssm_conv - 1,
                                                 cfg.ssm_inner), holds="conv"),
                 Store("ssm", SLOT, lambda bs: (cfg.ssm_state, cfg.ssm_inner),
                       jnp.float32, holds="scan")),
                None, _mamba_mixer, acts=(("memory", cfg.ssm_inner),)),
            "window": CacheKind(
                n("window"), "attn",
                tuple(Store(s, RING, head, holds="ring", positions=-2,
                            flat=True) for s in ("wk", "wv")),
                paired(("wk", "wv"), cfg.attn_window,
                       "window_paged_attention"), _differential_mixer),
            "full": CacheKind(
                1, "attn", tuple(Store(s, BLOCKS, head, positions=-2)
                                 for s in ("k", "v")),
                shared, _differential_mixer),
            "cross": CacheKind(n("cross"), "attn", (), shared,
                               _differential_mixer),
            # a gated memory unit gates the last state-space layer's scan
            "gmu": CacheKind(n("gmu"), "gmu", (), None, lambda *_: (
                lambda h, lp, flat, li, nth, acts: (
                    HY.gmu(h, lp, acts["memory"]), flat, acts))),
        }
        return {k: v for k, v in table.items() if v.layers}
    # the standard block: under kinds its calls are told apart by name and
    # scope, take one table a slot and the operands in their own type
    rope = cfg.pos_emb == "rope"
    pack = 2 if of_kinds and cfg.head_dim == 64 and cfg.kv_heads % 2 == 0 \
        else 1
    # two or three KV heads as wide as the lanes: heads first, so that the
    # block's second-minor dimension is its positions. A block ``[bs, 2,
    # 128]`` has a second-minor dimension of 2, which a tile of 8 (16 for
    # bfloat16) rows pads four (eight) times over in whatever Mosaic's
    # copies slice (a head narrower than the lanes is padded whatever the
    # order, and one head is no dimension)
    heads_first = of_kinds and 1 < cfg.kv_heads // pack < 4 \
        and pack * cfg.head_dim % 128 == 0

    def block(bs):
        heads = cfg.kv_heads // pack
        return (heads, bs, pack * cfg.head_dim) if heads_first \
            else (bs, heads, pack * cfg.head_dim)

    def kv(cls, names, **kw):
        return tuple(Store(s, cls, block, **kw,
                           **({"positions": -2} if heads_first else {}))
                     for s in names)

    def grouped(stores, window, name, scope, rotates):
        return Attend(stores, name if of_kinds else "paged_attention",
                      window, pack * cfg.head_dim, own_dtype=of_kinds,
                      by_slot=of_kinds, scope=scope if of_kinds else None,
                      rope=cfg.rope_dim if rope and rotates else 0,
                      pack=pack, heads_first=heads_first)

    table = {
        "window": CacheKind(
            n("window"), "attn", kv(RING, ("wk", "wv"), holds="ring"),
            grouped(("wk", "wv"), cfg.attn_window, "swa_attention", "swa",
                    True), _grouped_mixer),
        "full": CacheKind(
            n("full") if kinds else 0 if cfg.mla
            else cfg.loop_passes * cfg.num_layers, "attn",
            kv(BLOCKS, ("k", "v")),
            grouped(("k", "v"), None, "global_attention", "global",
                    cfg.rope_of("full") is not None), _grouped_mixer),
        "latent": CacheKind(
            n("latent") if kinds else cfg.num_layers if cfg.mla else 0,
            "attn",
            (Store("latent", BLOCKS, lambda bs: (bs, latent_row_width(cfg)),
                   positions=-2),),
            Attend(("latent",), "latent_paged_attention", None,
                   cfg.kv_lora_rank, by_slot=of_kinds,
                   rope=cfg.qk_rope_head_dim if rope else 0), _latent_mixer),
        "conv": CacheKind(
            n("conv"), "conv",
            (Store("conv", CONV, lambda bs: (cfg.conv_taps - 1,
                                             cfg.hidden_size), holds="conv"),),
            None, _conv_mixer,
            # rows that close a run (a decode row, a chunk's last): each
            # writes its slot's state in every conv layer
            span=lambda decode_rows, chunk_starts, rows, bucket, *_: {
                "conv_state_rows": decode_rows + len(chunk_starts)}),
        "kda": CacheKind(
            n("kda"), "kda",
            (Store("kda", SLOT, lambda bs: HY.kda_state_shapes(cfg)[0],
                   jnp.float32, holds="rule"),
             Store("kda_conv", CONV, lambda bs: HY.kda_state_shapes(cfg)[1],
                   holds="conv")),
            None, _kda_mixer, span=_kda_span),
        "mamba2": CacheKind(
            n("mamba2"), "ssd",
            (Store("ssd", SLOT, lambda bs: HY.mamba2_state_shapes(cfg)[0],
                   jnp.float32, holds="ssd"),
             Store("ssd_conv", CONV,
                   lambda bs: HY.mamba2_state_shapes(cfg)[1], holds="conv")),
            None, _mamba2_mixer,
            span=functools.partial(_ssd_span, cfg.mamba2_chunk)),
        # a layer that is a feed-forward part alone (a stack of single
        # sublayers): nothing kept, nothing mixed
        "ffn": CacheKind(n("ffn"), "mlp", (), None, lambda *_: None),
        # keys and values as a ``full`` layer's and, at the same (block,
        # offset), the indexer's key of every position (``index_row_width``)
        "sparse": CacheKind(
            n("sparse"), "attn",
            kv(BLOCKS, ("k", "v")) + (
                Store("idx", BLOCKS, lambda bs: (bs, index_row_width(cfg)),
                      positions=-2),),
            grouped(("k", "v"), None, "sparse_attention", "sparse", True),
            _sparse_mixer,
            span=functools.partial(_sparse_span, n("sparse"),
                                   cfg.sparse_topk),
            # a row's scores and its choice, float32 each
            tick_bytes=lambda rows, reach: 8 * rows * reach),
    }
    return {k: v for k, v in table.items() if v.layers}


def stack_kinds(cfg: T.TransformerConfig,
                seg: T.TransformerConfig) -> Tuple[str, ...]:
    """The kind of every layer of a segment of ``cfg``'s stack, in order:
    its own ``layer_kinds``, its period as often as it has steps, or the
    homogeneous stack's one kind."""
    return seg.layer_kinds or seg.period * seg.num_layers \
        or tuple(cache_kinds(cfg)) * seg.num_layers


def pool_stores(cfg: T.TransformerConfig) -> List[Tuple[int, Store]]:
    """(layers that keep it, store) of every array of ``cfg``'s pool."""
    return [(kind.layers, s) for kind in cache_kinds(cfg).values()
            for s in kind.stores]


def init_paged_kv(cfg: T.TransformerConfig, n_blocks: int, block_size: int,
                  dtype=None, state_slots: int = 0, max_run: int = 0
                  ) -> Dict[str, jax.Array]:
    """What a model keeps of its sequences between ticks, as a dict of
    arrays: every store of every entry of :func:`cache_kinds`, laid out by
    its class (:class:`Store`), each ``[layers that keep it, rows, ...]``
    (``forward_paged`` carries every one flat, ``[layers * rows, ...]``,
    and a layer owns the range that starts at ``layer * rows``).

    A sequence's slot is its first block's id (``tables[:, 0]``: the
    engine's allocator hands first blocks out of ``1 .. state_slots``);
    slot 0 is the pad rows' trash, as block 0 is. ``max_run``: the longest
    run of rows one sequence can have in a tick (:func:`ring_blocks`)."""
    dt = dtype or cfg.compute_dtype
    if cfg.layer_kinds and state_slots < 1:
        raise ValueError("layers of more than one kind in one stack keep "
                         "rings and state per sequence: state_slots >= 1 "
                         f"(got {state_slots})")
    pool = {}
    for layers, s in pool_stores(cfg):
        unit = s.unit(block_size)
        if s.cls == CONV:
            pool[s.name] = _conv_store(layers, state_slots, unit,
                                       s.dtype or dt)
            continue
        lead = {BLOCKS: (n_blocks,), SLOT: (state_slots + 1,), RING: (
            state_slots + 1, ring_blocks(cfg, block_size, max_run))}[s.cls]
        pool[s.name] = jnp.zeros(
            (layers,) + ((math.prod(lead),) if s.flat else lead) + unit,
            s.dtype or dt)
    return pool


def store_bytes(cfg: T.TransformerConfig, pool: Dict[str, Any]
                ) -> List[Tuple[Store, int]]:
    """(store, its bytes) of every array of ``pool`` (arrays, or their
    shapes): what is no ``BLOCKS`` is a sequence slot's, whatever its
    sequence's length."""
    return [(s, math.prod(pool[s.name].shape) * pool[s.name].dtype.itemsize)
            for _, s in pool_stores(cfg)]


#: words of scalar memory a tick's block tables may take: the kernels hold
#: them there beside the rows' slots and lengths, a row of a table padded to
#: whole lane tiles of 128 words, and every call copies them in: an eighth
#: of the 1 MB (262,144 words) a v5e core's holds
TABLE_WORDS = 1 << 15


def tick_tables(rows: int, blocks_a_table: int) -> int:
    """The most sequences (its pad rows' trash sequence among them) a tick
    of ``rows`` rows may hold where the calls take one table a sequence and
    no store of the pool says how many slots there are (:func:`_tick_of`):
    one a row, or as many tables of ``blocks_a_table`` entries as scalar
    memory has room for (``TABLE_WORDS``). The engine holds its sequence
    slots under it."""
    return max(1, min(rows, TABLE_WORDS // (-(-blocks_a_table // 128) * 128)))


def _slots(cfg: T.TransformerConfig, pool: Dict[str, jax.Array]) -> int:
    """``slots + 1`` of ``pool``, read off a store by its class: a SLOT
    store's or a RING's rows a layer, else a CONV store's over its layers'
    inputs; 0 for a pool of blocks alone."""
    stores = pool_stores(cfg)
    for _, s in stores:
        if s.cls == SLOT or (s.cls == RING and not s.flat):
            return pool[s.name].shape[1]
    return next((pool[s.name].shape[0] // (layers * s.unit(0)[0])
                 for layers, s in stores if s.cls == CONV), 0)


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
                              heads_first: bool = False,
                              chosen: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Pure-XLA paged attention (the CPU/fallback path; the Pallas kernel in
    ``ops/pallas/paged_attention.py`` computes the same thing without
    materializing the gathered KV).

    q [T, N, D]; pools [NB, bs, K, D]; tables [T, MB]; lengths [T] (= pos+1).
    Token t attends to its sequence's first ``lengths[t]`` cache slots.
    ``alibi``: [N] slopes — cache slot c IS absolute position c, so the
    bias is ``slope · (c − (lengths−1))`` (matches ``cached_attention``).
    ``scale`` / ``window`` / ``heads_first`` (pools [NB, K, bs, D]): as
    the kernel's (``paged_attention``). ``chosen`` [T, MB * bs] bool: token
    t attends to cache slot c only where it is set (a sparse layer's
    choice).
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
    if chosen is not None:
        mask &= chosen[:, None, :]
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


#: what ``forward_paged(attention_fn=)`` reads as "no kernel"
_REFERENCES = (None, paged_attention_reference, latent_attention_reference)


def tick_attention(cfg: T.TransformerConfig, use_kernel: bool
                   ) -> Tuple[Callable, int]:
    """The attention a tick of ``cfg`` runs over its pool, and the rows of
    that kernel's tile (0 where the plain-jnp reference runs): the ONE
    place that maps (model, kernels wanted) to a function. The Pallas
    kernel has no bias input yet, so ALiBi ticks take the reference
    (correct, rectangular-gather cost); a latent pool takes the kernel's
    latent instantiation, which is the kernel with one KV head.

    Pools of keys and values: ``fn(q, kpool, vpool, tables, lengths,
    ...)``; the latent pool: ``fn(q_row, pool, tables, lengths, kvr,
    scale)``. What a kind's call tells the function beside its operands
    (the call's name, a window, the block's layout, the products' type, a
    table a row or a slot) is its entry's (:class:`Attend`)."""
    call = next((kind.attend for kind in cache_kinds(cfg).values()
                 if kind.attend is not None), None)
    latent = call is not None and call.stores == ("latent",)
    if call is None or not use_kernel or cfg.pos_emb == "alibi":
        return (latent_attention_reference if latent
                else paged_attention_reference), 0
    from deepspeed_tpu.ops.pallas.paged_attention import (
        latent_paged_attention, paged_attention, tile_rows)

    return (latent_paged_attention if latent else paged_attention), \
        tile_rows(cfg.num_heads, call.width)


def tick_walks(cfg: T.TransformerConfig, pool: Dict[str, jax.Array]
               ) -> List[Tuple[int, Optional[int], int]]:
    """(layers, window, cache positions a fetch step) of each kind of call
    :func:`tick_attention`'s kernel makes in a tick of ``cfg`` over
    ``pool``: what ``ops.pallas.paged_attention.count_steps`` needs, beside
    a tick's lengths, to say how many fetch steps the tick walks. The step
    is the kernel's own rule of the operands' shapes (``_geometry``);
    kinds that make the same call over the same stores are one walk."""
    from deepspeed_tpu.ops.pallas.paged_attention import _geometry

    units = {s.name: len(s.unit(0)) for _, s in pool_stores(cfg)}
    walks: Dict[Attend, int] = {}
    for kind in cache_kinds(cfg).values():
        if kind.attend is not None:
            walks[kind.attend] = walks.get(kind.attend, 0) + kind.layers
    out = []
    for call, layers in walks.items():
        # a call sees one block of each pool and the queries' heads
        blocks = [jax.ShapeDtypeStruct(
            (1,) + pool[n].shape[-units[n]:], pool[n].dtype)
            for n in call.stores]
        q = jax.ShapeDtypeStruct(
            (1, cfg.num_heads, blocks[0].shape[-1]), blocks[0].dtype)
        _, bs, _, P = _geometry(q, blocks, call.width, call.heads_first)
        out.append((layers, call.window, bs * P))
    return out


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
    latent = {k: lp[k] for k in ("latent_down", "latent_up") if k in lp}
    return dropless_moe_ffn(
        h, lp["gate_w"], experts, cfg.activation, cfg.moe_top_k,
        score_func=cfg.moe_score_func, route_norm=cfg.moe_route_norm,
        route_scale=cfg.moe_route_scale, shared=shared or None,
        gate_bias=lp.get("gate_bias"), n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group, valid=valid,
        layer=layer if stack else None,
        first_expert=cfg.moe_first_expert,
        route_norm_eps=cfg.moe_route_norm_eps, latent=latent or None)


class _Tick(NamedTuple):
    """A tick's rows as the skeleton derives them once for every layer."""
    positions: jax.Array    # [T]
    tables: jax.Array       # [T, MB] blocks within one layer's range
    block_idx: jax.Array    # [T] the block row t writes into
    offsets: jax.Array      # [T] its slot in that block
    lengths: jax.Array      # [T] cache slots row t attends to (= pos+1)
    valid: jax.Array        # [T] bool: no pad row (its table is all trash)
    bs: int                 # positions of a block
    # a row's sequence slot (its table's first block) and one table a slot;
    # a pool without slots: the row's own index and the rows' tables, or,
    # where the calls take a table a sequence all the same, the sequence's
    # number in the tick (:func:`tick_tables`)
    slot: jax.Array         # [T]
    by_slot: jax.Array      # [S1, tick_tables + 1 or T, MB]
    S1: int                 # slots + 1; 0 without
    # where sequences keep state: their rows' runs, and the read and write
    # of a store of convolution inputs (:func:`_conv_rows`)
    runs: Optional[HY.Runs]
    conv: Optional[Tuple[Callable, Callable]]
    rope: Dict[str, Tuple[jax.Array, jax.Array]]   # kind -> (cos, sin)
    attend: Callable        # :func:`tick_attention`'s choice
    kernels: bool           # kernels are wanted


def _tick_of(cfg: T.TransformerConfig, kinds: Dict[str, CacheKind],
             pool: Dict[str, jax.Array], positions: jax.Array,
             tables: jax.Array, kernels: bool) -> _Tick:
    Tn, MB = tables.shape
    blocks = next(s for _, s in pool_stores(cfg) if s.cls in (BLOCKS, RING))
    bs = pool[blocks.name].shape[blocks.positions]
    block_idx = jnp.take_along_axis(
        tables, (positions // bs)[:, None], axis=1)[:, 0]
    # the rotary tables: a position lies within the tables' reach where
    # the call takes a table a slot, else the pool's
    rope = {kind: T.rope_table(
        bs * (MB if e.attend.by_slot else pool[e.attend.stores[0]].shape[1]),
        e.attend.rope, cfg.rope_theta, cfg.rope_scaling_dict)
        for kind, e in kinds.items() if e.attend and e.attend.rope}
    S1 = _slots(cfg, pool)
    slot, by_slot = jnp.arange(Tn, dtype=jnp.int32), tables
    runs = conv = None
    if S1:
        slot = tables[:, 0]
        by_slot = jnp.zeros((S1, MB), jnp.int32).at[slot].set(tables)
    elif any(e.attend and e.attend.by_slot for e in kinds.values()):
        # a pool of blocks alone keeps nothing a slot, so the tick counts
        # its sequences itself, from 1: a row starts one where its first
        # block is not the row's before (row 0 of the table stays the
        # trash block's, for rows a kernel pads its tiles with)
        start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                 tables[1:, 0] != tables[:-1, 0]])
        slot = jnp.cumsum(start, dtype=jnp.int32)
        by_slot = jnp.zeros((tick_tables(Tn, MB) + 1, MB), jnp.int32
                            ).at[slot].set(tables, mode="drop")
    if any(s.cls in (SLOT, CONV) for _, s in pool_stores(cfg)):
        runs = HY.runs_of(slot, positions)
        conv = _conv_rows(S1, slot, runs.last & (slot > 0))
    attend, tile = tick_attention(cfg, kernels)
    return _Tick(positions, tables, block_idx, positions % bs, positions + 1,
                 tables[:, 0] > 0, bs, slot, by_slot, S1, runs, conv, rope,
                 attend, tile > 0)


# A kind's mixer is built once a tick by its entry's ``mixer(cfg, tick: _Tick,
# pool: as stored, entry: CacheKind, kind: its name)`` and is ``mixer(h, lp,
# flat, li, nth, acts) -> (mixed [T, .] before wo, flat, acts)``. From the
# normed rows ``h [T, H]``, the layer's parameters and the flat pool carry
# (``forward_hidden``): project, write the tick's rows into the layer's
# range of its kind's stores, mix, and return the mixer's output with the
# new carry. ``li``: the layer's index in the stack; ``nth``: its index
# among its KIND's layers from the stack's first layer on (which block
# range, which ring, which state rows); ``acts``: what the tick's layers
# hand on (the state-space family's ``memory``): an activation, no cache.

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


def _kv_cache(tick: _Tick, pool: Dict[str, jax.Array], entry: CacheKind
              ) -> Tuple[Callable, Callable]:
    """The write and the walk of a kind that attends over keys and values,
    whatever the block's layout: ``write(flat, nth, k, v) -> (flat, the
    layer's tables)`` and ``attend(q, flat, tables, scale, **bias)``.

    A ``BLOCKS`` layer writes and walks its own block range through the
    rows' tables. A ``RING`` layer's cache is a ring of its sequence's slot:
    a tick's rows are written before any attends, so a row walks the ring
    through a table of its own (or its slot's), column ``c`` naming the
    slot's block ``c % RB``, which holds positions ``c*bs ..`` if any of
    them is inside the row's window."""
    call = entry.attend
    MB = tick.tables.shape[1]
    first = pool[call.stores[0]]
    if entry.stores and entry.stores[0].cls == RING:
        RB = math.prod(first.shape[1:first.ndim - 3]) // tick.S1
        rows_a_layer = tick.S1 * RB
        owners = jnp.arange(tick.S1, dtype=jnp.int32) if call.by_slot \
            else tick.slot
        tables = (owners * RB)[:, None] + (
            jnp.arange(MB, dtype=jnp.int32) % RB)[None, :]
        block = tick.slot * RB + (tick.positions // tick.bs) % RB
    else:
        rows_a_layer = first.shape[1]
        tables = tick.by_slot if call.by_slot else tick.tables
        block = tick.block_idx

    def write(flat, nth, k, v, **also):
        base = nth * rows_a_layer
        at, new = base + block, dict(flat)
        # blocked KV write (reference ragged_ops KV-copy kernels): token t
        # -> pool[base + block[t], offsets[t]]. Pad tokens hit the layer's
        # trash block (block 0 of its range: never allocated). Heads first:
        # (block, head, slot) index every written row, so that the
        # scatter's one window dim is the array's minor one
        index = (at[:, None], jnp.arange(k.shape[1])[None, :],
                 tick.offsets[:, None]) if call.heads_first \
            else (at, tick.offsets)
        for name, x in zip(call.stores, (k, v)):
            new[name] = flat[name].at[index].set(
                x.astype(flat[name].dtype), mode="drop")
        # what else the kind keeps a position, a row each: same place
        for name, x in also.items():
            new[name] = flat[name].at[at, tick.offsets].set(
                x.astype(flat[name].dtype), mode="drop")
        return new, tables + base

    def attend(q, flat, tables, scale=None, chosen=None, **bias):
        """``chosen``: a sparse layer's choice."""
        k, v = (flat[name] for name in call.stores)
        with contextlib.nullcontext() if call.scope is None \
                else jax.named_scope(call.scope):
            if tick.kernels:
                return tick.attend(
                    q, k, v, tables, tick.lengths, scale=scale,
                    window=call.window, heads_first=call.heads_first,
                    name=call.name,
                    mxu_dtype=q.dtype if call.own_dtype else jnp.float32,
                    row_table=tick.slot if call.by_slot else None,
                    **({} if chosen is None else {"chosen": chosen}))
            if chosen is not None:
                bias = {**bias, "chosen": chosen}
            return tick.attend(
                q, k, v, tables[tick.slot] if call.by_slot else tables,
                tick.lengths, scale=scale, window=call.window,
                heads_first=call.heads_first, **bias)

    return write, attend


def _grouped_mixer(cfg, tick, pool, entry, kind):
    """Grouped-query attention over per-head K/V (``full``: a block range
    a layer, ``window``: a ring): q/k/v projections with their biases,
    ``qk_norm``, rotary at the rows' positions where the kind rotates;
    ALiBi models (BLOOM/Falcon) bias the paged scores by head slope x
    relative position; then the elementwise output gate where the model
    has one."""
    dt = cfg.compute_dtype
    Tn = tick.positions.shape[0]
    call = entry.attend
    write, attend = _kv_cache(tick, pool, entry)
    bias = {}
    if cfg.pos_emb == "alibi":
        bias["alibi"] = T.alibi_slopes(cfg.num_heads) * cfg.alibi_bias_scale

    def mixer(h, lp, flat, li, nth, acts):
        q, k, v = _project_qkv(cfg, h, lp, tick.positions,
                               tick.rope.get(kind))
        scale, own = cfg.attn_scale or None, None
        if call.pack > 1:
            # heads of 64 lie two to a pool row (``kv_lane_pack``); the
            # scores' factor stays the unpacked head's
            k, v = (x.reshape(Tn, cfg.kv_heads // call.pack, -1)
                    for x in (k, v))
            q, own = _lane_packed(q, cfg.kv_heads)
            scale = cfg.score_scale
        flat, tables = write(flat, nth, k, v)
        attn = attend(q, flat, tables, scale, **bias)
        if own is not None:
            attn = own(attn)
        attn = attn.reshape(Tn, cfg.num_heads * cfg.head_dim)
        if cfg.attn_gate:
            attn = attn * jax.nn.sigmoid(h @ lp["wg"].astype(dt))
        return attn, flat, acts

    return mixer


def sparse_choice(scores: jax.Array, pos: jax.Array, lengths: jax.Array,
                  topk: int, axes: Tuple[int, ...], reach: int) -> jax.Array:
    """The positions each row of a tick attends to in a ``sparse`` layer,
    as a mask of ``scores``' shape: of a row's positions under its length
    the ``topk`` of the largest score, the lower position first among
    equals; all of them while it has no more than ``topk``. EXACT: the
    cut is the ``topk``-th largest score itself, found by a bisection over
    the scores' bits (32 counts of the entries at or over a candidate, the
    high halves of the words and then the low; the float32 order is the
    order of the bits once a negative's are flipped),
    and where equal scores straddle the cut a second bisection, over
    positions, keeps the lowest of them. No sort: a row's 18k scores are
    never ordered, only counted.

    ``scores`` float32 in any layout; ``pos``: each entry's position,
    ``lengths``: each row's length, both broadcast against it; ``axes``:
    the axes that run over a row's positions; ``reach``: positions lie
    under it. Nothing is chosen, and no count made, in a tick none of
    whose rows is longer than ``topk``."""
    valid = pos < lengths

    def count(x):
        return jnp.sum(x, axis=axes, keepdims=True, dtype=jnp.int32)

    def pick():
        bits = lax.bitcast_convert_type(scores, jnp.int32)
        # ascending in the score as an unsigned word; 0 is below every
        # valid entry's
        u = lax.bitcast_convert_type(
            bits ^ ((bits >> 31) & 0x7fffffff), jnp.uint32) \
            ^ jnp.uint32(0x80000000)
        u = jnp.where(valid, jnp.maximum(u, jnp.uint32(1)), jnp.uint32(0))

        def bisect(half, ahead):
            """The largest 16-bit ``h`` with ``ahead + count(half >= h) >=
            topk``, a bit a pass."""
            def bit(i, cut):
                cand = cut | (jnp.uint16(1) << (15 - i).astype(jnp.uint16))
                return jnp.where(ahead + count(half >= cand) >= topk,
                                 cand, cut)

            return lax.fori_loop(0, 16, bit, jnp.zeros_like(
                count(valid), jnp.uint16))

        # a word's halves one after the other: 32 passes over 16-bit
        # entries where the words whole would be read 32 times (a chunk
        # tick's 2,048 x 18k scores: 7.5 ms a layer on the v5e, bound by
        # the bytes)
        high = (u >> 16).astype(jnp.uint16)
        cut_high = bisect(high, 0)
        low = jnp.where(high == cut_high, u.astype(jnp.uint16),
                        jnp.uint16(0))
        cut_low = bisect(low, count(high > cut_high))
        cut = (cut_high.astype(jnp.uint32) << 16) \
            | cut_low.astype(jnp.uint32)
        over, equal = u > cut, (u == cut) & valid
        room = topk - count(over)

        def lowest():
            # the largest P with no more than ``room`` equals under it
            def bit(i, P):
                cand = P | (1 << (n_bits - 1 - i))
                return jnp.where(count(equal & (pos < cand)) <= room,
                                 cand, P)

            n_bits = max(reach, 2).bit_length()
            P = lax.fori_loop(0, n_bits, bit, jnp.zeros_like(room))
            return over | (equal & (pos < P))

        return valid & lax.cond(jnp.any(count(equal) > room), lowest,
                                lambda: over | equal)

    return lax.cond(jnp.any(lengths > topk), pick, lambda: valid)


def _sparse_mixer(cfg, tick, pool, entry, kind):
    """Grouped-query attention over the positions a learned indexer
    chooses (``cfg.sparse_topk`` a row; DeepSeek sparse attention). Keys
    and values are written as a ``full`` layer's, and beside them EVERY
    position's index key (``index_row_width``), whether or not the tick's
    rows are long enough to choose; then three parts, a scope each:

    * ``index``: the indexer's projections and its scores of every row at
      its sequence's positions (``ops.pallas.index_scores``: one walk a run
      of rows that share a table, one product a step, the heads summed f32);
    * ``select``: the exact ``topk`` of each row's own scores, as a mask
      (``ops.pallas.sparse_choice``: a tile of rows' scores read once and
      counted in VMEM; :func:`sparse_choice` is its plain form);
    * ``sparse``: a row walks its sequence's blocks as a ``full`` layer's
      rows do and takes the choice as one more term of every step's mask
      (``paged_attention(chosen=)``): it reads every position where it
      attends to ``topk`` (the span's ``sparse_positions_read`` against
      ``sparse_selected`` says by how much). A decode row as a chunk's: a
      gather of what a decode row chose was slower at the 17k positions
      it was measured at (PERF.md, PR 46) and is not here.

    Without kernels the same three parts in plain jnp."""
    Tn = tick.positions.shape[0]
    topk = cfg.sparse_topk
    write, attend = _kv_cache(tick, pool, entry)
    bs, MB = tick.bs, tick.tables.shape[1]
    N, D = cfg.num_heads, cfg.head_dim
    pad = index_row_width(cfg) - cfg.index_head_dim
    rope = T.rope_table(bs * MB, cfg.index_head_dim, cfg.rope_theta,
                        cfg.rope_scaling_dict)
    from deepspeed_tpu.ops.pallas import index_scores as IX
    from deepspeed_tpu.ops.pallas import sparse_choice as SC

    def mixer(h, lp, flat, li, nth, acts):
        q, k, v = _project_qkv(cfg, h, lp, tick.positions,
                               tick.rope.get(kind))
        with jax.named_scope("index"):
            qi, ki, w = (x[0] for x in T.index_projections(
                h[None], lp, cfg, rope, tick.positions[None]))
            qi = jnp.pad(qi, ((0, 0), (0, 0), (0, pad)))
        flat, tables = write(flat, nth, k, v,
                             idx=jnp.pad(ki, ((0, 0), (0, pad))))
        if not tick.kernels:
            with jax.named_scope("index"):
                scores = IX.index_scores_reference(
                    qi, w, flat["idx"], tables[tick.slot])
            with jax.named_scope("select"):
                chosen = sparse_choice(
                    scores, jnp.arange(bs * MB, dtype=jnp.int32)[None],
                    tick.lengths[:, None], topk, (1,), bs * MB)
            attn = attend(q, flat, tables, chosen=chosen)
            return attn.reshape(Tn, N * D), flat, acts
        with jax.named_scope("index"):
            # [S / C, T', C]: a lane tile of every row together
            scores = IX.index_scores(qi, w, flat["idx"], tables,
                                     tick.lengths, tick.slot)
        with jax.named_scope("select"):
            chosen = SC.sparse_choice(scores, tick.lengths, topk)
        attn = attend(q, flat, tables, chosen=chosen)
        return attn.reshape(Tn, N * D), flat, acts

    return mixer


def _sparse_span(layers: int, topk: int, decode_rows: int,
                 chunk_starts: List[int], rows: int, bucket: int,
                 lengths) -> Dict[str, int]:
    """What a tick's sparse layers score, choose and read, summed over
    the tick's real rows and (but for the last) the layers: the positions
    the indexer scores (a row's length) and those its walks fetch (a
    decode row's length, a chunk's longest row's: its rows share a walk),
    those chosen (``min(length, topk)``; the decode rows' apart), those
    the attention meets for them (a row walks its sequence with the choice
    as a mask: its length), and the rows longer than ``topk`` (the others
    attend to all they have); and the tiles of real rows the choice's kernel
    steps over, with those of them that count (a tile none of whose rows is
    longer than ``topk`` writes its rows' positions and counts nothing)."""
    from deepspeed_tpu.ops.pallas.sparse_choice import count_tiles

    total = int(lengths.sum())
    ends = chunk_starts[1:] + [rows]
    chosen = lengths.clip(max=topk)
    tiles, counting, _ = count_tiles(lengths, topk)
    return dict(
        sparse_layers=layers,
        index_positions=layers * total,
        index_walk_positions=layers * int(
            lengths[:decode_rows].sum() + sum(
                lengths[a:b].max() for a, b in zip(chunk_starts, ends))),
        sparse_selected=layers * int(chosen.sum()),
        sparse_selected_decode=layers * int(chosen[:decode_rows].sum()),
        sparse_positions_read=layers * total,
        sparse_rows_choosing=int((lengths > topk).sum()),
        sparse_choice_tiles=layers * tiles,
        sparse_choice_tiles_counting=layers * counting)


def _differential_mixer(cfg, tick, pool, entry, kind):
    """Differential attention as one grouped-query attention over paired
    heads (``hybrid.paired_queries``): a ``window`` layer over its ring, the
    ``full`` layer over the block pool, a ``cross`` layer with queries
    alone over what the ``full`` layer wrote."""
    dt = cfg.compute_dtype
    Tn = tick.positions.shape[0]
    N, K, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    write, attend = _kv_cache(tick, pool, entry)
    scale = D ** -0.5                       # of the unpaired heads

    def heads(h, w, n):
        return (h @ w.astype(dt)).reshape(Tn, n, D)

    def mixer(h, lp, flat, li, nth, acts):
        q = HY.paired_queries(heads(h, lp["wq"], N))
        tables = tick.tables
        if entry.stores:
            flat, tables = write(flat, nth, *(
                HY.paired_cache(heads(h, lp[w], K)) for w in ("wk", "wv")))
        o = attend(q, flat, tables, scale)
        return (HY.differential_merge(o, lp, li, cfg.norm_eps).astype(dt),
                flat, acts)

    return mixer


def _latent_mixer(cfg, tick, pool, entry, kind):
    """The MLA (DeepSeek) pool ``{"latent"}`` [L, NB, bs, W]: a row's
    latent (``c_kv ++ k_pe``, padded to the lanes) is what is written,
    and attention is weight-absorbed (:func:`_absorbed`; same math as the
    v1 engine's latent-cache decode)."""
    call = entry.attend
    Tn = tick.positions.shape[0]
    NB, _, W = pool["latent"].shape[1:]
    tables, which = (tick.by_slot, {"row_table": tick.slot}) \
        if call.by_slot else (tick.tables, {})

    def rope_fn(v):                                   # v [T, 1, n, dr]
        if kind not in tick.rope:
            return v           # the model rotates nothing (``mla_use_nope``)
        return T.apply_rope_at(v, *tick.rope[kind], tick.positions[:, None])

    row_pad = jnp.zeros(
        (Tn, W - cfg.kv_lora_rank - cfg.qk_rope_head_dim),
        pool["latent"].dtype)

    def mixer(h, lp, flat, li, nth, acts):
        plat, base = flat["latent"], nth * NB
        hB = h[:, None, :]                            # [T, 1, H]
        q = T._mla_q(hB, lp, cfg, rope_fn)[:, 0]      # [T, N, dn+dr]
        c_kv, k_pe = T._mla_latents(hB, lp, cfg, rope_fn)
        row = jnp.concatenate(
            [c_kv[:, 0].astype(plat.dtype),
             k_pe[:, 0, 0].astype(plat.dtype), row_pad], axis=-1)
        plat = plat.at[base + tick.block_idx, tick.offsets].set(
            row, mode="drop")
        attn = _absorbed(q, lp["wkv_b"], cfg, lambda q_row: tick.attend(
            q_row, plat, tables + base, tick.lengths,
            cfg.kv_lora_rank, mla_softmax_scale(cfg), **which))
        return (attn.reshape(Tn, cfg.num_heads * cfg.v_head_dim),
                {**flat, "latent": plat}, acts)

    return mixer


def _conv_mixer(cfg, tick, pool, entry, kind):
    """The gated short convolution: a layer reads its rows' runs' state
    from its sequence slots' rows and writes the state after each run's
    last row (``hybrid.short_conv``)."""
    read, write = tick.conv

    def mixer(h, lp, flat, li, nth, acts):
        mixed, conv = HY.short_conv(
            h, lp, tick.runs, read(flat["conv"], nth, cfg.conv_taps - 1))
        return mixed, {**flat, "conv": write(flat["conv"], nth, conv)}, acts

    return mixer


def _kda_mixer(cfg, tick, pool, entry, kind):
    """Kimi Delta Attention: the convolutions' inputs as a ``conv`` layer's
    and the rule's matrix inside ``hybrid.delta_rule`` (a run's is read at
    its first row and written after its last, in place)."""
    read, write = tick.conv
    slot, S1 = tick.slot, tick.S1

    def mixer(h, lp, flat, li, nth, acts):
        inputs, conv = HY.kda_inputs(
            h, lp, cfg, tick.runs,
            read(flat["kda_conv"], nth, cfg.kda_conv - 1))
        # a pad row's sequence is none: row 0 of the store
        o, state = HY.delta_rule(
            *inputs, tick.runs, flat["kda"],
            jnp.where(slot > 0, nth * S1 + slot, 0),
            use_kernel=tick.kernels)
        return HY.kda_output(o, h, lp, cfg), {
            **flat, "kda": state,
            "kda_conv": write(flat["kda_conv"], nth, conv)}, acts

    return mixer


def _kda_span(decode_rows: int, chunk_starts: List[int], rows: int,
              bucket: int, *_) -> Dict[str, int]:
    """The rule's two forms by the program's own rule
    (``hybrid.delta_rule``): runs of one row, up to the one-row form's
    count, and the rows of every other run; the chunk form's grid steps by
    the kernel's own rule (``ops.pallas.kda.count_pieces``): every run but
    the first ``kda_step_rows`` runs of one row (decode rows lie first, a
    row each); and the rows that close a run, each of which writes its
    slot's state in every kda layer."""
    from deepspeed_tpu.ops.pallas.kda import count_pieces

    step, chunk_runs = _two_forms(decode_rows, chunk_starts, rows, bucket,
                                  HY.KDA_STEP_ROWS)
    return dict(kda_step_rows=step, kda_chunk_rows=rows - step,
                kda_chunk_pieces=count_pieces(chunk_runs),
                kda_state_rows=decode_rows + len(chunk_starts))


def _two_forms(decode_rows: int, chunk_starts: List[int], rows: int,
               bucket: int, most: int) -> Tuple[int, List[Tuple[int, int]]]:
    """A tick's rows between a recurrence's two forms, by the program's own
    rule (``hybrid._runs_of_one``): (the rows the one-row form takes: runs
    of one row, decode rows first, up to ``most``; the (first row, rows) of
    every run left to the other form)."""
    runs = [(a, b - a) for a, b in zip(chunk_starts,
                                       chunk_starts[1:] + [rows])]
    step = min(decode_rows + sum(n == 1 for _, n in runs), bucket, most)
    took = min(decode_rows, step)
    left = step - took                  # for the prompts' runs of one
    chunk_runs = [(r, 1) for r in range(took, decode_rows)]
    for a, n in runs:
        if n == 1 and left:
            left -= 1
        else:
            chunk_runs.append((a, n))
    return step, chunk_runs


def _mamba2_mixer(cfg, tick, pool, entry, kind):
    """Mamba-2: the convolution's inputs as a ``conv`` layer's and the
    recurrence's matrices inside ``hybrid.ssd`` (a run's are read at its
    first row and written after its last, in place)."""
    read, write = tick.conv
    slot, S1 = tick.slot, tick.S1

    def mixer(h, lp, flat, li, nth, acts):
        (x, *rest), z, conv = HY.mamba2_inputs(
            h, lp, cfg, tick.runs,
            read(flat["ssd_conv"], nth, cfg.mamba2_conv - 1))
        # a pad row's sequence is none: row 0 of the store
        y, state = HY.ssd(
            x, *rest, tick.runs, flat["ssd"],
            jnp.where(slot > 0, nth * S1 + slot, 0),
            chunk=cfg.mamba2_chunk, use_kernel=tick.kernels)
        return HY.mamba2_output(y, x, z, lp, cfg), {
            **flat, "ssd": state,
            "ssd_conv": write(flat["ssd_conv"], nth, conv)}, acts

    return mixer


def _ssd_span(chunk: int, decode_rows: int, chunk_starts: List[int],
              rows: int, bucket: int, *_) -> Dict[str, int]:
    """The recurrence's two forms by the program's own rule
    (``hybrid.ssd``): runs of one row, up to the one-row form's count, and
    the rows of every other run; the chunked form's pieces
    (``ops.pallas.ssd.count_pieces``); and the rows that close a run, each
    of which writes its slot's state in every mamba2 layer."""
    from deepspeed_tpu.ops.pallas.ssd import count_pieces

    step, chunk_runs = _two_forms(decode_rows, chunk_starts, rows, bucket,
                                  HY.SSD_STEP_ROWS)
    return dict(ssd_step_rows=step, ssd_chunk_rows=rows - step,
                ssd_chunk_pieces=count_pieces(chunk_runs, chunk),
                ssd_state_rows=decode_rows + len(chunk_starts))


def _mamba_mixer(cfg, tick, pool, entry, kind):
    """The selective state-space layer: its scan output is the ``memory``
    the ``gmu`` layers gate."""
    read, write = tick.conv
    slot, S1, runs = tick.slot, tick.S1, tick.runs

    def mixer(h, lp, flat, li, nth, acts):
        at = nth * S1 + slot
        out, memory, conv, ssm = HY.mamba(
            h, lp, cfg, runs, read(flat["conv"], nth, cfg.ssm_conv - 1),
            flat["ssm"][at])
        # the state after a run's last row is its sequence's; the
        # other rows' index lies past the array and is dropped
        put = jnp.where(runs.last, at, flat["ssm"].shape[0])
        flat = {**flat, "conv": write(flat["conv"], nth, conv),
                "ssm": flat["ssm"].at[put].set(ssm, mode="drop")}
        return out, flat, {**acts, "memory": memory}

    return mixer


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
    (gathered from the last hidden state, before the final norm; a looped
    stack's: from every pass's normed state) and the
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


def head_logits(params: PyTree, x: jax.Array, cfg: T.TransformerConfig,
                with_exit: bool = False):
    """The head over rows of the last hidden state: final norm and the
    vocabulary matmul, [rows, vocab] fp32.

    A looped stack's ``x`` is every pass's state of those rows, ``[rows,
    passes, H]``, normed already (:func:`forward_hidden`): its exit gates
    choose the pass whose state feeds the head (``T.loop_exit``: on these
    rows alone, never on logits a pass) and nothing is normed again.
    ``with_exit``: (logits, the rows' exit distribution ``[rows, passes]``
    float32)."""
    pdf = None
    if cfg.loop_passes > 1:
        x, pdf = T.loop_exit(params, x, cfg)
    with jax.named_scope("lm_head"):
        if pdf is None:
            x = T._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = T.lm_logits(x, T._lm_head_of(params, cfg), cfg)
        if cfg.lm_head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
    return (logits, pdf) if with_exit else logits


def forward_hidden(params: PyTree, tokens: jax.Array, positions: jax.Array,
                   tables: jax.Array, pool: Dict[str, jax.Array],
                   cfg: T.TransformerConfig,
                   attention_fn: Optional[Callable] = None):
    """:func:`forward_paged` up to the head: (the last hidden state [T, H],
    updated pool, stats).

    One skeleton for every model: embed, the rows' blocks and lengths
    (:class:`_Tick`), one block (``ln1``, the mixer of the layer's kind,
    ``wo``, the residual form, FFN or experts) stepped a PERIOD of kinds at
    a time by ``T.scan_periods`` over each segment of ``cfg.segments``
    (leading dense layers, then the stack; a homogeneous segment is one
    run of period 1), the head. What a layer's mixer projects, writes into
    the pool and attends to is its kind's entry of :func:`cache_kinds`.

    ``attention_fn`` says whether kernels are wanted: ``None`` or a
    reference means no, anything else yes; which function then runs is
    ``cfg``'s to say, not the caller's (:func:`tick_attention`).

    Expert layers run dropless (:func:`_tick_experts`); ``stats`` is
    ``{"expert_rows": ...}`` for a model with experts, else ``{}``.

    A looped stack (``cfg.loop_passes`` = R > 1) scans its segments R times
    over the SAME leaves (``xs`` is never copied a pass): pass ``t``'s
    layers write and walk the cache layers from ``t * num_layers``, the
    final norm follows every pass, and the state returned is every pass's,
    ``[T, R, H]`` (so ``x[rows]`` gathers rows as of any model's), for
    :func:`head_logits` to choose from.
    """
    from deepspeed_tpu.ops.quantization import dequant_params

    dt = cfg.compute_dtype
    kinds = cache_kinds(cfg)

    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]             # [T, H]
        if cfg.emb_multiplier != 1.0:
            x = x * jnp.asarray(cfg.emb_multiplier, dt)
        if cfg.pos_emb == "learned":
            x = x + params["pos_emb"].astype(dt)[positions]
        if cfg.emb_norm:
            x = T._norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)

    tick = _tick_of(cfg, kinds, pool, positions, tables,
                    attention_fn not in _REFERENCES)
    mixers = {kind: entry.mixer(cfg, tick, pool, entry, kind)
              for kind, entry in kinds.items()}

    def block(seg, kind, x, lp, flat, li, nth, acts, experts):
        """One layer: ``x + wo(Mixer(ln1 x))``, then the FFN or the
        experts on the sequential or a parallel residual, post-norms
        where the block has them. ``experts``: (the whole stack's
        matrices, the layer's index among them)."""
        lp = dequant_params(lp, dt)       # weight-only quant: per-layer dequant
        # a stack of single sublayers (``seg.one_sublayer``): a layer is
        # its mixer alone, or (kind ``ffn``) the part below alone on the
        # layer's one norm
        resid, h = x, None
        if kind != "ffn":
            with jax.named_scope(kinds[kind].scope):
                h = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
                mixed, flat, acts = mixers[kind](h, lp, flat, li, nth, acts)
                out = mixed @ lp["wo"].astype(dt)
                if seg.use_bias:
                    out = out + lp["bo"].astype(dt)
                if seg.post_norms:
                    out = T._norm(out, lp["ln1_post"], seg.norm,
                                  seg.norm_eps)
                resid = x + T.scale_residual(out, seg)
            if seg.one_sublayer:
                return resid, flat, acts, None
        # ``mlp`` is a dense FFN's scope; an expert layer's operations
        # carry ``router`` / ``experts`` / ``shared_experts``
        with contextlib.nullcontext() if seg.n_experts \
                else jax.named_scope("mlp"):
            # the parallel residual norms the block's input (or shares
            # ``ln1``'s output), the sequential one what the mixer left
            if kind == "ffn":
                h2 = T._norm(x, lp["ln1"], seg.norm, seg.norm_eps)
            elif not seg.parallel_block:
                h2 = T._norm(resid, lp["ln2"], seg.norm, seg.norm_eps)
            elif seg.shared_parallel_norm:
                h2 = h
            else:
                h2 = T._norm(x, lp["ln2"], seg.norm, seg.norm_eps)
            if seg.n_experts:
                down, n_rows = _tick_experts(h2, lp, seg, tick.valid,
                                             *experts)
            else:
                down, n_rows = T._ffn(h2, lp, seg)[0], None
            if seg.post_norms:
                down = T._norm(down, lp["ln2_post"], seg.norm, seg.norm_eps)
        return resid + T.scale_residual(down, seg), flat, acts, n_rows

    # the kind of every layer of the stack: ``nth`` counts a kind's layers
    # from its first
    whole = sum((stack_kinds(cfg, seg) for _, seg in cfg.segments), ())

    def body_of(seg, first, taken, stack, ahead):
        """A step of a segment for ``T.scan_periods``: its period's layers,
        one :func:`block` each. ``first``: the segment's first layer's
        index in the stack; ``taken``: first layer of a run -> the scan
        steps taken before it (the carry counts steps from the stack's
        first: no division finds a layer's index or its ``nth``);
        ``ahead``: kind -> the cache layers of the kind that earlier passes
        of a looped stack own."""
        # the one thing that is a family's own: where a period's step finds
        # layer i's leaves: stacked by layer under kinds of the standard
        # block, else by step already (a homogeneous stack's one layer a
        # step, the state-space family's by kind)
        layer_of = T.period_layer if seg.layer_kinds else (
            lambda lps, period, i: lps[period[i]] if seg.period else lps)

        def of_run(period, run_first):
            # a layer's index and its ``nth`` are what the run starts from,
            # less its earlier steps' share, plus the count's
            P, before = len(period), taken[run_first]
            li0 = first + run_first - before * P
            per = {k: period.count(k) for k in set(period)}
            nth0 = {k: ahead[k] + whole[:first + run_first].count(k)
                    - before * per[k] for k in per}

            def body(carry, lps):
                x, flat, step, acts = carry
                n_rows = []
                for i, kind in enumerate(period):
                    li = li0 + step * P + i
                    # (a kind's only layer is its 0th)
                    nth = 0 if kinds[kind].layers == 1 else nth0[kind] \
                        + step * per[kind] + period[:i].count(kind)
                    # the layer's index among the experts' stack: its
                    # own in the segment, or among the ``ffn`` layers
                    x, flat, acts, rows_e = block(
                        seg, kind, x, layer_of(lps, period, i), flat, li,
                        nth, acts,
                        (stack, nth if seg.one_sublayer else li - first))
                    n_rows.append(rows_e)
                if seg.one_sublayer:    # the ``ffn`` layers' alone
                    n_rows = [r for r in n_rows if r is not None]
                    return (x, flat, step + 1, acts), \
                        jnp.stack(n_rows) if n_rows else None
                # (a period of one layer has nothing to stack)
                return (x, flat, step + 1, acts), None \
                    if not seg.n_experts else n_rows[0] \
                    if P == 1 else jnp.stack(n_rows)

            return body

        return of_run

    # The pool rides the layer scans as a FLAT [L*NB, bs, ...] carry that is
    # scattered in place (layer l owns block range [l*NB, (l+1)*NB)); the
    # attention kernel gathers through layer-offset tables, reading only the
    # listed blocks. Threading per-layer slices as scan xs→ys (the naive
    # layout) re-stacks the ENTIRE pool every call — measured 25 ms/tick at
    # 512 blocks inside a decode scan, linear in pool size — where the
    # in-place carry touches only the written rows. (A ring's rows are
    # blocks too; a store of convolution inputs is rows already.)
    flat = {s.name: pool[s.name] if s.cls == CONV else pool[s.name].reshape(
        (-1,) + pool[s.name].shape[-len(s.unit(0)):])
        for _, s in pool_stores(cfg)}
    acts = {name: jnp.zeros((x.shape[0], width), dt)
            for kind in kinds.values() for name, width in kind.acts}
    stats, states = {}, []
    for t in range(cfg.loop_passes):   # once; a looped stack: R times
        carry = (x, flat, jnp.int32(0), acts)
        first = steps = 0
        ahead = {k: t * whole.count(k) for k in kinds}
        with T.pass_scope(cfg, t):
            for key, seg in cfg.segments:
                # the experts' matrices stay out of the scan's sliced
                # operands: the grouped matmul takes the stack whole
                # (``moe.layer.grouped_dot``; a slice is a copy of a
                # layer's experts before each matmul); quantised leaves
                # ({"q", "scale", ...}) are dequantised a layer at a time
                # and stay in
                held = params[key]["ffn"] if seg.one_sublayer \
                    else params[key]
                stack = {k: v for k, v in held.items() if seg.n_experts
                         and k in _EXPERT_LEAVES and hasattr(v, "ndim")}
                xs = {k: v for k, v in params[key].items() if k not in stack}
                if seg.one_sublayer:
                    xs["ffn"] = {k: v for k, v in held.items()
                                 if k not in stack}
                layers, taken = stack_kinds(cfg, seg), {}
                for at, _, n in T.kind_runs(layers):
                    taken[at], steps = steps, steps + n
                carry, n_rows = T.scan_periods(
                    body_of(seg, first, taken, stack, ahead), carry, xs,
                    layers, by_step=not seg.layer_kinds)
                first += len(layers)
                if seg.n_experts:
                    # [steps, period, E] (or [steps, E]) a run -> [expert
                    # layers, E]
                    n_rows = [r.reshape((-1, r.shape[-1])) for r in n_rows
                              if r is not None]
                    stats["expert_rows"] = n_rows[0] if len(n_rows) == 1 \
                        else jnp.concatenate(n_rows)
        x, flat = carry[:2]
        if cfg.loop_passes > 1:
            # the final norm after EVERY pass: it feeds the next
            x = T.loop_norm(x, params, cfg)
            states.append(x)
    if states:
        x = jnp.stack(states, axis=1)                       # [T, R, H]
    return x, {k: flat[k].reshape(v.shape) for k, v in pool.items()}, stats
