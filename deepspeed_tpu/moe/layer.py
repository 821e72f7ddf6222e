"""MoE expert layer — dropless ragged dispatch + dense GShard fallback.

Parity: reference ``deepspeed/moe/layer.py`` (``MoE`` :17) and
``sharded_moe.py`` (``MOELayer`` :536, ``_AllToAll`` :97). The reference
dispatches with an explicit all-to-all over the expert-parallel process group;
here expert weights carry the 'expert' logical axis (sharded over the 'expert'
mesh axis by ``parallel/partitioning.py``) and the dispatch einsum's sharding
makes GSPMD emit the same all-to-all on ICI — no hand-written collective.

Two dispatch modes (``dispatch=`` / ``TransformerConfig.moe_dispatch``):

* ``ragged`` (default when available) — DROPLESS: sort token-choices by
  expert, one grouped matmul per weight via ``lax.ragged_dot`` (MXU-tiled by
  Mosaic), combine by inverse-permutation gather. No capacity, no dropped
  tokens, no [T,E,C] one-hot tensors — the MegaBlocks idea, TPU-style.
  Under token-sharded meshes the sort runs per-shard inside ``shard_map``
  (a global argsort would gather the batch); under expert parallelism a
  fixed-capacity all-to-all moves packed token buffers between expert
  shards (capacity is per expert-SHARD — E/ep coarser than per-expert, so
  drops are far rarer than the dense path at equal capacity_factor; i.e.
  ragged is only fully dropless OFF expert-parallel meshes — under EP a
  skewed router can still overflow the buffer, observable via
  :func:`set_drop_monitor` / the engine's periodic drop warning).
* ``dense`` — capacity-factor GShard dispatch/combine einsums: tokens →
  [E, C, H] buffers, expert FFNs as one batched einsum over the (sharded)
  E dim. Static shapes everywhere; drops beyond capacity. Kept as the
  reference-parity path and for meshes ragged doesn't cover.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.ad_checkpoint import checkpoint_name as _ckpt_name
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
    ZSHARD_AXIS,
    already_manual_axes,
    maybe_mesh,
    on_reset_mesh,
)
from deepspeed_tpu.moe.gating import (
    GateOutput,
    IndexGateOutput,
    _normalized,
    topk_gating,
    topk_gating_indices,
)
from deepspeed_tpu.ops.pallas.unwritten import unwritten

PyTree = Any

# jitted shard_map programs keyed on (mesh, static config, shapes) — eager
# callers would otherwise rebuild + retrace the program every invocation.
# Cleared when the global mesh is torn down: stale Mesh keys would pin the
# old mesh + its compiled programs for the life of the process.
_SHARDED_FN_CACHE: Dict[Any, Any] = {}

on_reset_mesh(_SHARDED_FN_CACHE.clear)

# Installed observer for EP-dispatch buffer overflows (None → no callback is
# traced, zero cost). Under expert parallelism the 'dropless' path is only
# dropless per destination SHARD: a skewed router can overflow the fixed
# all-to-all buffer and the overflowed choices silently fall through to the
# residual. The engine installs a monitor so that degradation is visible.
_DROP_MONITOR = None


def held_meter(rows: jax.Array, pairs: int,
               tile: Optional[int]) -> jax.Array:
    """What one call of a layer that holds a SHARE of its experts
    (:func:`moe_ffn`, ``first_expert=``) says of itself, as ONE
    ``int32[held + 2]`` that rides out of the compiled step beside the
    auxiliary loss (``moe_ffn(with_meter=True)``; no host callback: a
    program that holds one is never written to JAX's persistent cache):
    the rows each held expert got, then the (row, expert) pairs the router
    chose over all of its experts and the sorted rows a step of the
    layer's movers takes (:func:`held_tiles`; 0: the plain forms moved a
    row a pair). The last two are the call's static shapes; they ride
    with the rows so that a reader needs nothing else
    (:func:`read_held_meter`)."""
    return jnp.concatenate([rows.astype(jnp.int32), jnp.asarray(
        [pairs, tile or 0], jnp.int32)])


def read_held_meter(meter) -> Tuple[Any, int, Optional[int]]:
    """``(rows [held], pairs, tile or None)`` of one :func:`held_meter`."""
    return meter[:-2], int(meter[-2]), int(meter[-1]) or None


def set_drop_monitor(fn) -> None:
    """``fn(dropped_frac: float)`` called (async, via jax.debug.callback)
    with the global fraction of token-choices dropped by the EP buffer on
    each dispatch. Pass None to uninstall. Trace-time gated: install BEFORE
    the step is compiled."""
    global _DROP_MONITOR
    _DROP_MONITOR = fn


def _expert_constraint(x: jax.Array, n_lead: int = 1) -> jax.Array:
    """Constrain the leading expert dim onto the 'expert' mesh axis (if present)."""
    mesh = maybe_mesh()
    if mesh is None or mesh.shape.get(EXPERT_AXIS, 1) <= 1:
        return x
    spec = [None] * x.ndim
    spec[0] = EXPERT_AXIS
    return lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _dense_ffn(xt: jax.Array, w_up: jax.Array, w_down: jax.Array,
               w_gate: Optional[jax.Array], activation: str) -> jax.Array:
    """Plain FFN on flat tokens [T,H] (the shared-expert path)."""
    dt = xt.dtype
    up = xt @ w_up.astype(dt)
    gate = None if w_gate is None else xt @ w_gate.astype(dt)
    return _expert_act(up, gate, activation) @ w_down.astype(dt)


def _expert_act(up: jax.Array, gate: Optional[jax.Array], activation: str
                ) -> jax.Array:
    """The file's ONE form of an expert's activation, for the routed and
    the shared experts, serving and training (its derivative is autodiff's:
    ``relu2``'s is ``2 relu(up)``)."""
    if gate is not None:
        return jax.nn.silu(gate) * up
    if activation == "gelu":
        return jax.nn.gelu(up, approximate=True)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.relu(up)


def _pick_tile(dim: int, prefer: int) -> Optional[int]:
    """Tile of a grouped matmul's ROWS (megablox wants ``M % tm == 0``): the
    whole dim when it fits ``prefer``, else ``prefer`` where it divides,
    else the largest power of two below it, down to 128, that does; None
    when nothing divides (caller falls back to lax.ragged_dot)."""
    if 0 < dim <= prefer:
        return dim
    if dim % prefer == 0:
        return prefer
    t = 1 << (prefer.bit_length() - 1)   # largest pow2 <= prefer
    while t >= 128:
        if dim % t == 0:
            return t
        t //= 2
    return None


#: a [tk, tn] tile of bf16 weights, double-buffered, that still fits the
#: scoped VMEM beside a 128-row tile of rows and the accumulator
_WHOLE_TILE_ELEMS = 2048 * 1536


def _whole_k_tile(K: int, N: int, itemsize: int = 2
                  ) -> Optional[Tuple[int, int]]:
    """(tk, tn) of the serving form's weight tile: ``[K, N]`` whole where
    that fits VMEM (``_WHOLE_TILE_ELEMS`` elements of two bytes), else K
    whole and the widest power-of-two part of N, from 128 columns up, that
    does; None where not even that fits."""
    room = _WHOLE_TILE_ELEMS * 2 // itemsize
    tn = N
    while K * tn > room and tn % 256 == 0:
        tn //= 2
    return (K, tn) if K * tn <= room else None


#: what a grouped matmul's tiles may ask of VMEM inside a program: the
#: scoped limit's default on a v5e (16 MiB) less the MiB Mosaic keeps for
#: itself beside them (tiles of 15.88 MiB by the formula below asked for
#: 16.96 and were refused). Raising the limit through libtpu lost a whole
#: step 16 % (56.3k -> 47.2k tokens/s: it also governs XLA's own fusions)
_GMM_VMEM_BYTES = 15 * 2 ** 20


def gmm_vmem_bytes(product: str, tiles: Tuple[int, int, int],
                   itemsize: int) -> int:
    """The VMEM a grouped matmul's tiles ask for: two buffers of each
    operand's tile and of the result's, and the float32 accumulator.
    ``"gmm"``: ``[tm, tk] @ [tk, tn] -> [tm, tn]``; ``"tgmm"`` (the
    matrices' gradient): ``[tm, tk]^T @ [tm, tn] -> [tk, tn]``."""
    tm, tk, tn = tiles
    if product == "tgmm":
        ins, out = tm * (tk + tn), tk * tn
    else:
        ins, out = tm * tk + tk * tn, tm * tn
    return 2 * itemsize * (ins + out) + 4 * out


def _lane_divisors(dim: int) -> Tuple[int, ...]:
    """The parts of a dimension a tile may take with no remainder, widest
    first: its divisors that are multiples of the 128 lanes (the whole
    dimension among them where it is one)."""
    return tuple(t for t in range(dim - dim % 128, 0, -128) if dim % t == 0)


def gmm_tile_candidates(product: str, m: int, k: int, n: int, itemsize: int):
    """Every ``(tm, tk, tn)`` a product of contraction ``k`` and columns
    ``n`` over ``m`` rows may run under: ``tm`` divides the rows (256, 512
    or 128 of them: in :func:`gmm_tiles`' order of preference), ``tk`` and
    ``tn`` divide their dimensions by lanes (megablox's remainder path
    casts both operands to float32 and masks them in the last step of
    every contraction), and the tiles fit the scoped VMEM."""
    rows = [t for t in (256, 512, 128) if m % t == 0]
    return [(tm, tk, tn) for tm in rows for tk in _lane_divisors(k)
            for tn in _lane_divisors(n)
            if gmm_vmem_bytes(product, (tm, tk, tn), itemsize)
            <= _GMM_VMEM_BYTES]


def gmm_moved_elements(product: str, tiles: Tuple[int, int, int], m: int,
                       k: int, n: int, groups: int) -> int:
    """Elements a grouped matmul's pipeline moves between HBM and VMEM
    under ``tiles``, every row in a group. ``"gmm"`` (grid: column tiles,
    row tiles, contraction steps): the rows are read again for every
    column tile, the result is written once, and the matrices are fetched
    once a GROUP where a step holds the whole contraction (the next row
    tile of the group asks for the block VMEM holds) but once a VISIT of
    a row tile (every tile, and again for each group that ends in one)
    where it is cut. ``"tgmm"`` (grid: column tiles, row-side tiles, row
    tiles): each operand is read again for every tile of the other's
    side."""
    tm, tk, tn = tiles
    if product == "tgmm":
        return m * k * (n // tn) + m * n * (k // tk) + groups * k * n
    fetches = groups if tk == k else m // tm + groups - 1
    return m * k * (n // tn) + fetches * k * n + m * n


def gmm_tiles(product: str, m: int, k: int, n: int, groups: int,
              itemsize: int) -> Optional[Tuple[int, int, int]]:
    """The tiles of ONE product of a grouped matmul, from its own shapes:
    of the candidates (:func:`gmm_tile_candidates`: no remainder, inside
    the scoped VMEM) the one that moves the fewest bytes
    (:func:`gmm_moved_elements`); where several move the same, 256 rows a
    tile before 512 before 128. None where there is no candidate.

    Measured alone on the v5e at the training cell's layer (``tools/
    gmm_kernel_alone.py``: 131,072 sorted rows, 32,768 of them in 16 groups
    the busiest of which holds 6.5 times the mean, ``[2304, 896]`` and
    ``[896, 2304]`` bfloat16; us a call, 687 at the MXU's peak; PERF.md,
    PR 52), the order fell the same way in all six shapes of product:
    the matrices whole in VMEM (256, 2304, 896) 816 and (256, 896, 2304)
    831 against the contraction cut (512, 1152, 896) 940, (256, 1152, 896)
    1,108 (the matrices then cross HBM once a row tile: 0.98 ms of bytes)
    and the columns cut (256, 896, 1152) 868, (512, 896, 256) 1,318 (the
    rows re-read nine times); the matrices' gradient (256, 1152, 896) 905
    against (512, 256, 896) 1,264 (the cotangent read nine times). Equal
    bytes: 512 rows a tile multiply a tenth more rows than 16 groups'
    edges hold (940 against 905), 128 take twice the steps (959)."""
    fits = gmm_tile_candidates(product, m, k, n, itemsize)
    # (``min`` keeps the first of equals: the candidates' order of rows)
    return min(fits, default=None, key=lambda t: gmm_moved_elements(
        product, t, m, k, n, groups))


def gmm_tilings(M: int, K: int, N: int, groups: int, itemsize: int):
    """``(forward, rows' gradient, matrices' gradient)`` tiles of ``[M, K]
    x [groups, K, N]``, or None where one of the three has none (then
    ``lax.ragged_dot``): the rows' gradient contracts N into K columns, the
    matrices' gradient has K on its rows' side and N for its columns."""
    tilings = (gmm_tiles("gmm", M, K, N, groups, itemsize),
               gmm_tiles("gmm", M, N, K, groups, itemsize),
               gmm_tiles("tgmm", M, K, N, groups, itemsize))
    return tilings if all(tilings) else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def tiled_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
              tilings: Tuple[Tuple[int, int, int], ...],
              interpret: bool = False) -> jax.Array:
    """megablox's grouped matmul with tiles of its own for each of its
    three products (``megablox.ops.gmm`` hands the forward's triple to both
    backward products: the rows' gradient then runs with K and N swapped
    under tiles cut for the other way round). ``tilings``: as
    :func:`gmm_tilings` gives them. The Mosaic calls keep megablox's names
    (``gmm``, ``tgmm``: the benchmark's roofline finds them by name)."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    return backend.gmm(x, w, group_sizes, x.dtype, tilings[0],
                       interpret=interpret)


def _tiled_gmm_fwd(x, w, group_sizes, tilings, interpret):
    return tiled_gmm(x, w, group_sizes, tilings, interpret), \
        (x, w, group_sizes)


def _tiled_gmm_bwd(tilings, interpret, res, grad):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    x, w, group_sizes = res
    dx = backend.gmm(grad, w, group_sizes, x.dtype, tilings[1],
                     transpose_rhs=True, interpret=interpret)
    dw = backend.tgmm(x.swapaxes(0, 1), grad, group_sizes, w.dtype,
                      tilings[2], num_actual_groups=w.shape[0],
                      interpret=interpret)
    return dx, dw, None


tiled_gmm.defvjp(_tiled_gmm_fwd, _tiled_gmm_bwd)


def grouped_dot(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                layer: Optional[jax.Array] = None,
                rows_share: float = 1.0) -> jax.Array:
    """Grouped GEMM ``x[rows of group e] @ w[e]`` → [M, N].

    ``rows_share``: the part of the ``M`` rows the groups are expected to
    take (a share of an expert layer: the pairs on experts that are not
    here lie past ``sum(group_sizes)`` and are never multiplied), for the
    serving form's choice of tile only.

    ``layer`` (serving): ``w`` is a layer stack's ``[L, E, K, N]`` and the
    (traced) index says whose experts to use. The stack goes to the kernel
    whole, as ``L*E`` groups of which only that layer's have rows: a slice
    of it is a copy of the layer's experts, because a custom call's operand
    is materialised (a third of the device's busy time in a Moonlight tick:
    1.1 GB a layer read and written beside the kernel's own read; PERF.md,
    PR 27). That form is forward-only, and where its rows are few an
    expert (``M / E`` within one 128-row tile: 48 in a 512-row tick of 64
    experts, six a row) the m tile is 128 (a tile is revisited for every
    group in it) and K and N stay whole where a ``[K, N]`` tile fits VMEM;
    measured on the v5e at ``[3072, 2048] x [64, 2048, 1408]``: (128, 2048,
    1408) 1.24 ms, a training ladder's (512, 1024, 128) 3.35 ms.

    Every other call on a TPU (training, and a serving call with many rows
    an expert) is megablox's two kernels under :func:`tiled_gmm`: the
    forward, the rows' gradient and the matrices' gradient each under the
    tiles :func:`gmm_tiles` gives for that product's own contraction and
    columns. ONE triple for all three (``megablox.ops.gmm``'s way), cut
    from the forward's ``(M, K, N)`` in powers of two, is (512, 256, 896)
    at the training cell's ``[131072, 2304] x [16, 2304, 896]`` (a quarter
    of the rows in the groups): nine contraction steps a row tile with the
    matrices fetched again for each, the rows' gradient with K and N
    swapped under tiles cut the other way round (a masked remainder step,
    a third of its column tiles empty), the cotangent read nine times for
    the matrices' gradient. Measured alone on the v5e, us a call, that
    triple -> each product's own (``tools/gmm_kernel_alone.py``; PERF.md,
    PR 52): forward 1,123 -> 816 (``w_down`` 1,306 -> 829), rows' gradient
    1,643 -> 831 (1,830 -> 819), matrices' gradient 1,265 -> 905 (1,289 ->
    927), for 687 at the MXU's peak. Elsewhere (the CPU; a width no
    multiple of 128 divides; rows no tile of 128 divides)
    ``lax.ragged_dot``.

    NOTE: rows past ``sum(group_sizes)`` are zeros under ragged_dot but
    UNDEFINED under gmm — callers must not read them (the EP path never
    gathers them back; the local path has no tail rows).
    """
    M, K = x.shape
    N = w.shape[-1]
    if layer is not None:
        L, E = w.shape[:2]
        w = w.reshape(L * E, K, N)
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((L * E,), group_sizes.dtype), group_sizes,
            (layer * E,))
        tm = _pick_tile(M, 128)
        tile = _whole_k_tile(K, N, w.dtype.itemsize)
        if jax.default_backend() == "tpu" and tm and tile \
                and M * rows_share <= 128 * E:
            from jax.experimental.pallas.ops.tpu.megablox import gmm

            return gmm(x, w, group_sizes, x.dtype, (tm,) + tile)
    if jax.default_backend() == "tpu":
        tilings = gmm_tilings(M, K, N, w.shape[0], x.dtype.itemsize)
        if tilings:
            return tiled_gmm(x, w, group_sizes, tilings)
    return lax.ragged_dot(x, w, group_sizes)


def ragged_expert_ffn(x_sorted: jax.Array, group_sizes: jax.Array,
                      experts: Dict[str, jax.Array], activation: str,
                      layer: Optional[jax.Array] = None,
                      rows_share: float = 1.0,
                      live_rows: Optional[Tuple[jax.Array, int]] = None
                      ) -> jax.Array:
    """Grouped expert FFN on expert-sorted tokens.

    x_sorted [M, H] — rows grouped contiguously by expert; group_sizes [E]
    int32 summing to M. Each weight application is ONE grouped GEMM
    (:func:`grouped_dot`) instead of E small matmuls or a [T,E,C] einsum.
    ``layer``, ``rows_share``: :func:`grouped_dot`'s (the leaves are a
    layer stack's; the groups take that part of the rows).
    ``live_rows``: (``sum(group_sizes)``, rows a step) of a share of an
    expert layer (:func:`_held_routed`): the activation and its gradient
    run over the row tiles below that many alone (:func:`held_expert_act`),
    as the grouped matmuls do.
    """
    dt = x_sorted.dtype
    x_gate = x_sorted
    if live_rows is not None and "w_gate" in experts:
        x_sorted, x_gate = held_two_readers(x_sorted, *live_rows)
    # named so remat="moe_selective" can store up/act (backward then never
    # re-runs the grouped GEMMs); measured slower than recompute on v5e at
    # the bench shapes, kept for bigger-expert configs where the trade flips
    up = _ckpt_name(
        grouped_dot(x_sorted, experts["w_up"].astype(dt), group_sizes,
                    layer, rows_share), "moe_up")
    g = (_ckpt_name(
        grouped_dot(x_gate, experts["w_gate"].astype(dt), group_sizes,
                    layer, rows_share), "moe_up")
        if "w_gate" in experts else None)
    act = _ckpt_name(
        _expert_act(up, g, activation) if live_rows is None
        else held_expert_act(up, g, live_rows[0], activation, live_rows[1]),
        "moe_act")
    return grouped_dot(act, experts["w_down"].astype(dt), group_sizes,
                       layer, rows_share)


def expert_sort(flat: jax.Array, E: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Counting sort of expert assignments → (order, inverse, counts).

    ``order[i]`` = row of the i-th element in expert-sorted layout (stable);
    ``inv[r]`` = sorted slot of row r (the inverse permutation, free here);
    ``counts[e]`` = occupancy of expert e (= ragged_dot group_sizes).

    A general ``argsort`` of 16k keys costs ~2.5 ms on a v5e (measured) —
    the single biggest cost of the naive sort-based dispatch. With E small
    the one-hot + cumsum counting sort is a few hundred µs and also
    produces counts + inverse without further sorts.
    """
    Tk = flat.shape[0]
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)        # [Tk, E]
    within = jnp.cumsum(onehot, axis=0) - 1                  # pos within expert
    counts = jnp.sum(onehot, axis=0)                         # [E]
    starts = jnp.cumsum(counts) - counts                     # exclusive
    slot = jnp.take_along_axis(within, flat[:, None], 1)[:, 0] \
        + jnp.take(starts, flat)
    slot = slot.astype(jnp.int32)
    order = jnp.zeros((Tk,), jnp.int32).at[slot].set(
        jnp.arange(Tk, dtype=jnp.int32))
    return order, slot, counts.astype(jnp.int32)


@jax.custom_vjp
def permute_rows(x: jax.Array, perm: jax.Array, inv_perm: jax.Array
                 ) -> jax.Array:
    """``x[perm]`` for a PERMUTATION ``perm`` whose inverse is known.

    XLA transposes a plain gather into a scatter-add (slow, serialized on
    TPU); for a permutation the transpose is just a gather by the inverse —
    this custom VJP tells XLA so, keeping both directions pure gathers.
    """
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (perm, inv_perm)


def _permute_rows_bwd(res, g):
    perm, inv_perm = res
    return jnp.take(g, inv_perm, axis=0), None, None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _take_pad_zero(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` where ``idx == len(x)`` (one-past sentinel) reads a zero row."""
    pad = jnp.zeros((1,) + x.shape[1:], x.dtype)
    return jnp.take(jnp.concatenate([x, pad], axis=0), idx, axis=0)


@jax.custom_vjp
def buffer_exchange(vals: jax.Array, fwd_idx: jax.Array, bwd_idx: jax.Array
                    ) -> jax.Array:
    """``vals[fwd_idx]`` (sentinel → 0) whose transpose is ``g[bwd_idx]``.

    For the EP pack/unpack buffers the forward and backward index maps are
    each other's (partial) inverses — slots are filled by at most one row —
    so both directions are pure gathers, never TPU scatter-adds.
    """
    return _take_pad_zero(vals, fwd_idx)


def _buffer_exchange_fwd(vals, fwd_idx, bwd_idx):
    return _take_pad_zero(vals, fwd_idx), bwd_idx


def _buffer_exchange_bwd(bwd_idx, g):
    return _take_pad_zero(g, bwd_idx), None, None


buffer_exchange.defvjp(_buffer_exchange_fwd, _buffer_exchange_bwd)


@jax.custom_vjp
def buffer_exchange_kdup(x: jax.Array, fwd_rows: jax.Array,
                         bwd_idx2d: jax.Array) -> jax.Array:
    """:func:`buffer_exchange` with the k-duplication folded into the index
    map (the EP-path sibling of :func:`dispatch_gather`): ``out[j] =
    x[fwd_rows[j]]`` where ``fwd_rows = slot2row // k`` — the one-past
    sentinel ``t*k`` divides to exactly ``t``, the zero pad row — so the
    [t*k, H] broadcast of x is never materialized. Transpose:
    ``dx[t] = Σ_c zero-padded g[bwd_idx2d[t, c]]`` — pure gathers.
    """
    return _take_pad_zero(x, fwd_rows)


def _buffer_exchange_kdup_fwd(x, fwd_rows, bwd_idx2d):
    return _take_pad_zero(x, fwd_rows), bwd_idx2d


def _buffer_exchange_kdup_bwd(bwd_idx2d, g):
    t, k = bwd_idx2d.shape
    dx = _take_pad_zero(g, bwd_idx2d.reshape(t * k)) \
        .reshape(t, k, g.shape[-1]).sum(axis=1)
    return dx, None, None


buffer_exchange_kdup.defvjp(_buffer_exchange_kdup_fwd,
                            _buffer_exchange_kdup_bwd)


@jax.custom_vjp
def dispatch_gather(x: jax.Array, order: jax.Array, inv2d: jax.Array
                    ) -> jax.Array:
    """Expert-sorted token rows WITHOUT materializing the k-duplicated
    [T*k, H] intermediate: ``out[j] = x[order[j] // k]`` in one gather.

    ``inv2d`` [T, k] is the inverse map (sorted slot of token t's c-th
    choice); the transpose is then also pure gathers:
    ``dx[t] = Σ_c g[inv2d[t, c]]`` — never a TPU scatter-add.
    """
    k = inv2d.shape[-1]
    return jnp.take(x, order // k, axis=0)


def _dispatch_gather_fwd(x, order, inv2d):
    return dispatch_gather(x, order, inv2d), inv2d


def _dispatch_gather_bwd(inv2d, g):
    return jnp.take(g, inv2d, axis=0).sum(axis=1), None, None


dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def combine_gather(y_s: jax.Array, weights: jax.Array, order: jax.Array,
                   inv2d: jax.Array) -> jax.Array:
    """Weighted combine straight from the expert-sorted rows:
    ``out[t] = Σ_c weights[t, c] · y_s[inv2d[t, c]]`` — the gate-weight
    multiply and the k-way reduction fuse into the un-sort gather, skipping
    two [T*k, H] materializations (the weighted rows and the un-sorted
    rows). Backward is pure gathers: ``dy_s[j] = w[j] · g[order[j] // k]``
    and ``dw[t, c] = ⟨y_s[inv2d[t, c]], g[t]⟩``.
    """
    w = weights.astype(y_s.dtype)
    return (jnp.take(y_s, inv2d, axis=0) * w[..., None]).sum(axis=1)


def _combine_gather_fwd(y_s, weights, order, inv2d):
    return combine_gather(y_s, weights, order, inv2d), \
        (y_s, weights, order, inv2d)


def _combine_gather_bwd(res, g):
    y_s, weights, order, inv2d = res
    k = inv2d.shape[-1]
    w_s = jnp.take(weights.reshape(-1), order).astype(y_s.dtype)
    dy = jnp.take(g, order // k, axis=0) * w_s[:, None]
    dw = jnp.einsum("tkh,th->tk", jnp.take(y_s, inv2d, axis=0), g,
                    preferred_element_type=jnp.float32).astype(weights.dtype)
    return dy, dw, None, None


combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def _ragged_dispatch_local(xt: jax.Array, weights: jax.Array, idx: jax.Array,
                           experts: Dict[str, jax.Array], activation: str,
                           layer: Optional[jax.Array] = None) -> jax.Array:
    """Dropless dispatch on local tokens: sort → ragged matmul → un-sort.

    xt [T, H]; weights/idx [T, k]. Dispatch = :func:`dispatch_gather`
    (one gather straight from [T, H], k-duplication folded into the index
    map); combine = :func:`combine_gather` (gate weights + k-reduction
    fused into the inverse gather) — no [T*k, H] broadcast, weighted copy
    or un-sorted copy is ever materialized, and no direction is a TPU
    scatter-add.
    """
    T, H = xt.shape
    k = idx.shape[-1]
    Tk = T * k
    E = experts["w_up"].shape[-3]
    flat = idx.reshape(Tk)
    order, inv, group_sizes = expert_sort(flat, E)
    # tiny [Tk] ints + [T,k] weights: named so the selective remat policy
    # STORES them — bwd then skips re-running the whole gate + counting sort
    order = _ckpt_name(order, "moe_gate")
    inv2d = _ckpt_name(inv.reshape(T, k), "moe_gate")
    group_sizes = _ckpt_name(group_sizes, "moe_gate")
    weights = _ckpt_name(weights, "moe_gate")
    x_s = dispatch_gather(xt, order, inv2d)
    y_s = ragged_expert_ffn(x_s, group_sizes, experts, activation, layer)
    return combine_gather(y_s, weights.astype(xt.dtype), order, inv2d)


def held_group_sizes(idx: jax.Array, held: int, first_expert: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The sort of a tick's (row, expert) pairs for a layer that holds the
    ``held`` experts from ``first_expert`` of those ``idx`` [T, k] names:
    (order, inverse [T, k], rows of each HELD expert [held], which pairs
    fall on a held expert [T, k]). Pairs on held experts come first, by
    expert; the pairs on experts that are not here sort behind them, in
    no group: the grouped matmul spends no row on them."""
    local = idx - first_expert
    here = (local >= 0) & (local < held)
    order, inv, counts = expert_sort(
        jnp.where(here, local, held).reshape(-1), held + 1)
    return order, inv.reshape(idx.shape), counts[:held], here


@jax.custom_vjp
def held_dispatch_gather(x: jax.Array, order: jax.Array, inv2d: jax.Array,
                         here: jax.Array) -> jax.Array:
    """:func:`dispatch_gather` for a share of the experts
    (:func:`held_group_sizes`), the plain form: a row a PAIR is moved,
    ``out[j] = x[order[j] // k]`` for every sorted slot, held or not (a
    call of fewer pairs than a tile, and the oracle of
    :func:`held_rows_out`, which moves a row a HELD pair). The
    transpose takes a pair's cotangent only where the pair is HERE: a row
    behind the held experts' groups holds nothing of this share's (under the
    grouped matmul's kernel it is undefined where no group covers it), in
    the backward too, and never reaches ``dx``."""
    return jnp.take(x, order // inv2d.shape[-1], axis=0)


def _held_dispatch_gather_fwd(x, order, inv2d, here):
    return held_dispatch_gather(x, order, inv2d, here), (inv2d, here)


def _held_dispatch_gather_bwd(res, g):
    inv2d, here = res
    picked = jnp.take(g, inv2d, axis=0)
    return jnp.sum(jnp.where(here[..., None], picked, 0), axis=1), \
        None, None, None


held_dispatch_gather.defvjp(_held_dispatch_gather_fwd,
                            _held_dispatch_gather_bwd)


@jax.custom_vjp
def held_combine_gather(y_s: jax.Array, weights: jax.Array, order: jax.Array,
                        inv2d: jax.Array, here: jax.Array) -> jax.Array:
    """:func:`combine_gather` for a share of the experts, the plain form
    (every pair's row fetched, the absent masked: a call of fewer pairs
    than a tile, and the oracle of :func:`held_pairs_in`, which fetches a
    row a HELD pair): ``out[t] = sum over the pairs of t that are HERE of
    weights[t, c] * y_s[inv2d[t, c]]``.
    A pair that is not here is masked out of the sum, not weighted by zero
    (its row of ``y_s`` is another expert's result, or undefined); in the
    transpose its row gets a zero cotangent and its weight a zero
    gradient."""
    picked = jnp.where(here[..., None], jnp.take(y_s, inv2d, axis=0)
                       * weights.astype(y_s.dtype)[..., None], 0)
    return jnp.sum(picked, axis=1)


def _held_combine_gather_fwd(y_s, weights, order, inv2d, here):
    return held_combine_gather(y_s, weights, order, inv2d, here), \
        (y_s, weights, order, inv2d, here)


def _held_combine_gather_bwd(res, g):
    y_s, weights, order, inv2d, here = res
    k = inv2d.shape[-1]
    w_s = jnp.take(jnp.where(here, weights, 0).reshape(-1), order
                   ).astype(y_s.dtype)
    dy = jnp.take(g, order // k, axis=0) * w_s[:, None]
    picked = jnp.take(y_s, inv2d, axis=0)
    dw = jnp.where(here, jnp.einsum(
        "tkh,th->tk", jnp.where(here[..., None], picked, 0), g,
        preferred_element_type=jnp.float32), 0).astype(weights.dtype)
    return dy, dw, None, None, None


held_combine_gather.defvjp(_held_combine_gather_fwd,
                           _held_combine_gather_bwd)


#: sorted rows one step of a share's movers takes: what they move and
#: activate follows ``sum(group_sizes)`` to a tile of this many rows
HELD_TILE_ROWS = 512


def held_tiles(pairs: int, tokens: int) -> Optional[Tuple[int, int]]:
    """(sorted rows a step, rows of the call a step) of the movers of a
    share's rows (:func:`_held_routed`) for a call of ``tokens`` rows and
    ``pairs`` (row, expert) pairs, or None where the plain forms stay (a
    row a pair moved, the pairs that are not here masked).

    The rule reads the static shapes alone: the movers engage wherever a
    call has a tile of pairs to skip, ``pairs >= HELD_TILE_ROWS``, and its
    rows come in whole tiles (a step of the combine walks an eighth of the
    call's rows, between 64 and 512: few enough that a decode tick's 256
    rows end their walk where the held pairs end). A step is one trip of a
    device loop around XLA's own gather: Mosaic takes no slice of one row
    of a tiled array (``Slice shape along dimension 0 must be aligned to
    tiling (8)``, HBM to HBM too), so a kernel could move a row no finer
    than XLA does (~25 ns a row of 2,304; PERF.md, PR 50), and a trip of
    the loop leaves no gap in the device's line. Measured alone on the v5e
    (``tools/held_rows_alone.py``, us a call, plain -> movers; PERF.md
    section 5 has the table): the training step's 131,072 pairs of 2,304 at
    a quarter here, dispatch 4,891 -> 1,086, combine 6,697 -> 1,357,
    activation 1,027 -> 345; a chunk tick's 8,192-16,384 pairs at an eighth
    here (Trinity / Kimi-Linear / Keye), the three together 957 / 1,328 /
    1,049 -> 194 / 245 / 236; a decode tick's 1,024-2,048 pairs, 121 / 140 /
    122 -> 103 / 58 / 55. Under a tile of pairs there is nothing to skip."""
    tile = HELD_TILE_ROWS
    ttile = min(tokens, max(64, min(tokens // 8, tile)))
    if pairs < tile or pairs % tile or tokens % ttile:
        return None
    return tile, ttile


def _rows_of(a: jax.Array, at: jax.Array) -> jax.Array:
    """``a[at]`` along the rows for indices the sort made (every one a row
    of ``a``): no test of an index against the bounds, no fill."""
    return a.at[at].get(mode="promise_in_bounds")


def _over_live_rows(fn, n: jax.Array, tile: int, *operands: jax.Array
                    ) -> Tuple[jax.Array, ...]:
    """``fn`` over the tiles of ``tile`` rows that hold a row below ``n``,
    one trip of a device loop a tile (``ceil(n / tile)`` trips: a run-time
    count, static shapes): ``fn(*tiles)`` takes the tile of every operand
    ``[M, ...]`` and returns a tuple of ``[tile, ...]``; the results are
    ``[M, ...]``, written in place, and from the first tile wholly at or
    past ``n`` not written at all."""
    M = operands[0].shape[0]

    def tiles_at(start):
        return [lax.dynamic_slice_in_dim(a, start, tile, 0) for a in operands]

    outs = jax.eval_shape(lambda: fn(*tiles_at(0)))

    def trip(i, bufs):
        return tuple(lax.dynamic_update_slice_in_dim(b, v, i * tile, 0)
                     for b, v in zip(bufs, fn(*tiles_at(i * tile))))

    return lax.fori_loop(
        0, (n + tile - 1) // tile, trip, unwritten(
            [jax.ShapeDtypeStruct((M,) + o.shape[1:], o.dtype) for o in outs],
            operands))


@functools.partial(jax.jit, inline=True, static_argnames=("ttile",))
def _held_pairs_in(src: jax.Array, weights: Optional[jax.Array],
                   slots: jax.Array, here: jax.Array, ttile: int
                   ) -> jax.Array:
    """``out[t] = sum over the pairs c of t that are HERE of weights[t, c] *
    src[slots[t, c]]`` (``weights`` None: ones), fetching the held pairs'
    rows alone: products and sum in float32, in the order of ``c``.

    XLA's gather fetches every index it is handed, so the indices handed
    are the held pairs': a row's held slots move to the front of its ``k``,
    the rows are walked by how many they hold, the most first (a counting
    sort over ``k + 1`` counts), and a tile of ``ttile`` rows fetches a
    column of slots at a time, as many as its first row holds (a trip of an
    inner device loop a column, the sum carried in float32):
    ``sum(group_sizes)`` rows in all, and under ``ttile`` more wherever the
    count falls inside a tile. One gather of ``T`` rows puts the sums back
    in the rows' order. (One loop body for every count: a ``lax.switch``
    over ``k + 1`` static counts fetched a tile's columns in one gather,
    but cost the training step 14 s of compilation and left the device idle
    between a tile's conditional and the next: PERF.md, PR 50.)"""
    T, k = slots.shape
    H = src.shape[-1]
    rank = jnp.cumsum(here, axis=1) - 1
    to_front = here[:, :, None] & (rank[:, :, None] == jnp.arange(k))

    def held_first(a):                       # [T, k]: held pairs first
        return jnp.sum(jnp.where(to_front, a[:, :, None], 0), axis=1)

    # the walk: a counting sort of the rows by the pairs they do NOT hold
    # (one scatter of a row's slots and weights to its place: every gather
    # XLA compiles costs the step's set-up a sixth of a second)
    absent = k - jnp.sum(here, axis=1, dtype=jnp.int32)
    onehot = absent[:, None] == jnp.arange(k + 1)                # [T, k+1]
    sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    place = jnp.sum(jnp.where(
        onehot, jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
        + ends - sizes, 0), axis=1)
    c_all = k - jnp.sum(jnp.arange(T)[:, None] >= ends, axis=1,
                        dtype=jnp.int32)
    walk = [held_first(slots)] + ([] if weights is None else [
        lax.bitcast_convert_type(
            held_first(weights.astype(jnp.float32)), jnp.int32)])
    walk = jnp.zeros((T, len(walk) * k), jnp.int32).at[place].set(
        jnp.concatenate(walk, axis=1), unique_indices=True,
        mode="promise_in_bounds").T
    s_all = walk[:k]
    w_all = None if weights is None else lax.bitcast_convert_type(
        walk[k:], jnp.float32)

    def a_tile(i, out):
        c = lax.dynamic_slice_in_dim(c_all, i * ttile, ttile, 0)

        def a_column(r, acc):
            def of(a):
                return lax.dynamic_slice(a, (r, i * ttile), (1, ttile))[0]

            rows = _rows_of(src, of(s_all)).astype(jnp.float32)
            if w_all is not None:
                rows = rows * of(w_all)[:, None]
            return acc + jnp.where((r < c)[:, None], rows, 0)

        acc = lax.fori_loop(0, c[0], a_column,
                            jnp.zeros((ttile, H), jnp.float32))
        return lax.dynamic_update_slice_in_dim(
            out, acc.astype(src.dtype), i * ttile, 0)

    walked = lax.fori_loop(
        0, T // ttile, a_tile,
        unwritten([jax.ShapeDtypeStruct((T, H), src.dtype)], (src,))[0])
    return _rows_of(walked, place)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def held_rows_out(x: jax.Array, order: jax.Array, inv2d: jax.Array,
                  here: jax.Array, n: jax.Array, tiles: Tuple[int, int]
                  ) -> jax.Array:
    """:func:`held_dispatch_gather` over the held pairs' rows alone:
    ``out[j] = x[order[j] // k]`` for the sorted slots ``j`` below ``n =
    sum(group_sizes)`` (to a tile of ``tiles[0]`` rows: the held pairs sort
    first); the rows behind them are not read and not written. The
    transpose fetches a row's held pairs alone (:func:`_held_pairs_in`)."""
    return _rows_out(x, order, n, k=inv2d.shape[-1], tile=tiles[0])


# (the movers' bodies sit in inlined inner jits: a step traces a layer's
# forward and each transposition rule several times over, four layers
# long, and a body traced once is a cache hit after that)
@functools.partial(jax.jit, inline=True, static_argnames=("k", "tile"))
def _rows_out(x, order, n, *, k, tile):
    return _over_live_rows(lambda o: (_rows_of(x, o // k),),
                           n, tile, order)[0]


def _held_rows_out_fwd(x, order, inv2d, here, n, tiles):
    return held_rows_out(x, order, inv2d, here, n, tiles), (inv2d, here)


def _held_rows_out_bwd(tiles, res, g):
    inv2d, here = res
    return (_held_pairs_in(g, None, inv2d, here, tiles[1]),
            None, None, None, None)


held_rows_out.defvjp(_held_rows_out_fwd, _held_rows_out_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def held_pairs_in(y_s: jax.Array, weights: jax.Array, order: jax.Array,
                  inv2d: jax.Array, here: jax.Array, n: jax.Array,
                  tiles: Tuple[int, int]) -> jax.Array:
    """:func:`held_combine_gather` over the held pairs' rows alone
    (:func:`_held_pairs_in`: two of a row's eight fetched in the mean, not
    eight fetched and six masked). The transpose makes ``dy[j] = w[j] *
    g[order[j] // k]`` for the slots below ``n`` alone, and has ``y_s[j]``
    beside ``g``'s row there, so a weight's gradient ``<y_s[j], g[order[j]
    // k]>`` is one float32 a sorted row and ``dw`` a gather of scalars:
    no second gather of ``y_s`` by row."""
    return _held_pairs_in(y_s, weights, inv2d, here, tiles[1])


def _held_pairs_in_fwd(y_s, weights, order, inv2d, here, n, tiles):
    return held_pairs_in(y_s, weights, order, inv2d, here, n, tiles), \
        (y_s, weights, order, inv2d, here, n)


def _held_pairs_in_bwd(tiles, res, g):
    dy, dw = _pairs_in_transposed(*res, g, tile=tiles[0])
    return dy, dw, None, None, None, None


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def _pairs_in_transposed(y_s, weights, order, inv2d, here, n, g, *, tile):
    k = inv2d.shape[-1]
    w_flat = weights.reshape(-1)

    def a_tile(o, y):
        rows = _rows_of(g, o // k)
        dots = jnp.sum(y.astype(jnp.float32) * rows.astype(jnp.float32),
                       axis=-1)
        return rows * _rows_of(w_flat, o).astype(y.dtype)[:, None], dots

    dy, dots = _over_live_rows(a_tile, n, tile, order, y_s)
    return dy, jnp.where(here, _rows_of(dots, inv2d), 0).astype(weights.dtype)


held_pairs_in.defvjp(_held_pairs_in_fwd, _held_pairs_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def held_two_readers(x_s: jax.Array, n: jax.Array, tile: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """``x_s`` for each of its two readers (the grouped matmuls of ``up``
    and ``gate``), so that the sum of their two cotangents runs over the
    row tiles below ``n`` alone: left to the transposition it is one
    addition of two ``[T x k, H]`` arrays (2.7 ms a layer in the training
    cell, where a quarter of the rows holds anything)."""
    return x_s, x_s


def _held_two_readers_fwd(x_s, n, tile):
    return (x_s, x_s), n


def _held_two_readers_bwd(tile, n, g):
    return _sum_of_two(*g, n, tile=tile), None


@functools.partial(jax.jit, inline=True, static_argnames=("tile",))
def _sum_of_two(a, b, n, *, tile):
    return _over_live_rows(lambda a, b: (a + b,), n, tile, a, b)[0]


held_two_readers.defvjp(_held_two_readers_fwd, _held_two_readers_bwd)


def _act_operands(up, gate):
    return (up,) if gate is None else (up, gate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def held_expert_act(up: jax.Array, gate: Optional[jax.Array], n: jax.Array,
                    activation: str, tile: int) -> jax.Array:
    """:func:`_expert_act` over the row tiles below ``n`` alone, forward
    and backward; the rows behind them are not read and not written."""
    return _act_rows(up, gate, n, activation=activation, tile=tile)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("activation", "tile"))
def _act_rows(up, gate, n, *, activation, tile):
    return _over_live_rows(
        lambda u, g=None: (_expert_act(u, g, activation),),
        n, tile, *_act_operands(up, gate))[0]


def _held_expert_act_fwd(up, gate, n, activation, tile):
    return held_expert_act(up, gate, n, activation, tile), (up, gate, n)


def _held_expert_act_bwd(activation, tile, res, d):
    grads = _act_rows_transposed(*res, d, activation=activation, tile=tile)
    return grads[0], (None if res[1] is None else grads[1]), None


@functools.partial(jax.jit, inline=True,
                   static_argnames=("activation", "tile"))
def _act_rows_transposed(up, gate, n, d, *, activation, tile):
    def a_tile(d, *tiles):
        return jax.vjp(lambda u, g=None: _expert_act(u, g, activation),
                       *tiles)[1](d)

    return _over_live_rows(a_tile, n, tile, d, *_act_operands(up, gate))


held_expert_act.defvjp(_held_expert_act_fwd, _held_expert_act_bwd)


def _held_routed(xt: jax.Array, weights: jax.Array, idx: jax.Array,
                 experts: Dict[str, jax.Array], activation: str,
                 first_expert: int, router_experts: int,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """:func:`_ragged_dispatch_local` for a SHARE of the layer's experts
    (:func:`held_group_sizes`), for a serving tick and a training step
    alike: (this share's part of the routed result, the rows of each held
    expert), forward and backward (gradients reach the router through the
    weights of the pairs that are here).

    A row a pair is the SHAPE of every sorted array; the work is a row a
    HELD pair. The pairs that are here sort first, by expert, so they are
    the rows ``[0, n)``, ``n = sum(group_sizes)``, a value the sort leaves
    on the device; the pairs that are not here lie behind them, in no
    group. The grouped matmuls (``gmm`` forward and for the rows' gradient,
    ``tgmm`` for the matrices') spend no tile there, and neither do the
    dispatch (:func:`held_rows_out`), the activation
    (:func:`held_expert_act`), the combine (:func:`held_pairs_in`) or
    their transposes: each walks the row tiles below ``n`` and no other, so
    a step's time follows its routing a tile at a time, and ``T x k`` is
    the bound no routing outgrows (no capacity, no bucket, no pair
    dropped). The rows behind ``n`` are undefined, in the backward too: a
    pair that is not here is left out of every sum and every gradient, not
    weighted by zero. Where a call's pairs are too few for the tiles to
    pay (:func:`held_tiles`) the plain forms stay
    (:func:`held_dispatch_gather`, :func:`held_combine_gather`: a row a
    pair moved, the absent masked)."""
    held = experts["w_up"].shape[-3]
    order, inv2d, group_sizes, here = held_group_sizes(
        idx, held, first_expert)
    order = _ckpt_name(order, "moe_gate")
    inv2d = _ckpt_name(inv2d, "moe_gate")
    group_sizes = _ckpt_name(group_sizes, "moe_gate")
    weights = _ckpt_name(weights, "moe_gate").astype(xt.dtype)
    tiles = held_tiles(idx.size, idx.shape[0])
    if tiles is None:
        x_s = held_dispatch_gather(xt, order, inv2d, here)
        y_s = ragged_expert_ffn(x_s, group_sizes, experts, activation, layer,
                                rows_share=held / router_experts)
        return held_combine_gather(y_s, weights, order, inv2d, here), \
            group_sizes
    n = jnp.sum(group_sizes)
    x_s = held_rows_out(xt, order, inv2d, here, n, tiles)
    y_s = ragged_expert_ffn(x_s, group_sizes, experts, activation, layer,
                            rows_share=held / router_experts,
                            live_rows=(n, tiles[0]))
    return held_pairs_in(y_s, weights, order, inv2d, here, n, tiles), \
        group_sizes


def _token_axes(mesh) -> Tuple[Tuple[str, ...], Optional[str]]:
    """Mesh axes that shard the token stream: (batch axes, seq axis) —
    excluding axes an enclosing shard_map already made manual."""
    manual = already_manual_axes()
    batch = tuple(a for a in (DATA_AXIS, ZSHARD_AXIS, EXPERT_AXIS)
                  if mesh.shape.get(a, 1) > 1 and a not in manual)
    seq = SEQ_AXIS if (mesh.shape.get(SEQ_AXIS, 1) > 1
                       and SEQ_AXIS not in manual) else None
    return batch, seq


def ragged_mesh_plan(mesh, B: int, S: Optional[int], E: int):
    """How the ragged dispatch should lower on ``mesh`` for a [B,S,H] input.

    Returns ``('local', None)`` (plain program — no axis sharded),
    ``('shard', (batch_axes, seq_ax, ep, tp))`` (shard_map program), or
    ``('indivisible', None)`` (shapes don't divide the sharded mesh; caller
    decides between the dense path and the GSPMD-placed local program).
    The ONE copy of this predicate — used by both :func:`resolve_dispatch`
    and :func:`_ragged_routed` so auto-selection and lowering can't drift.
    """
    if mesh is None:
        return "local", None
    manual = already_manual_axes()
    batch_axes, seq_ax = _token_axes(mesh)
    ep = mesh.shape.get(EXPERT_AXIS, 1) if EXPERT_AXIS not in manual else 1
    tp = TENSOR_AXIS if (mesh.shape.get(TENSOR_AXIS, 1) > 1
                         and TENSOR_AXIS not in manual) else None
    if not (batch_axes or seq_ax or tp or ep > 1):
        return "local", None
    bshards = 1
    for a in batch_axes:
        bshards *= mesh.shape[a]
    if B % bshards or (seq_ax and (S is None or S % mesh.shape[seq_ax])) \
            or (ep > 1 and E % ep):
        return "indivisible", None
    return "shard", (batch_axes, seq_ax, ep, tp)


def resolve_dispatch(dispatch: str, rng: Optional[jax.Array],
                     noise_std: float, B: Optional[int] = None,
                     S: Optional[int] = None, E: Optional[int] = None) -> str:
    """'auto' → 'ragged' wherever it's implemented, else 'dense'.

    ragged covers: single shard, token-sharded meshes (per-shard sort in
    shard_map), and expert-parallel meshes (fixed-capacity all-to-all) —
    provided the batch/seq dims divide the mesh (shard_map is exact about
    shapes where GSPMD constraints are hints) and E divides the expert axis.
    Noisy gating stays dense: per-shard RNG streams inside shard_map would
    decorrelate from the global-batch reference semantics.
    """
    if dispatch not in ("auto", "ragged", "dense"):
        raise ValueError(
            f"moe dispatch must be auto|ragged|dense, got {dispatch!r}")
    noisy = rng is not None and noise_std > 0.0
    if dispatch == "ragged" and noisy:
        raise ValueError(
            "dispatch='ragged' does not implement noisy gating (per-shard "
            "RNG streams would decorrelate from global-batch semantics) — "
            "use dispatch='dense' or 'auto' with noisy gating")
    if dispatch != "auto":
        return dispatch
    if noisy:
        return "dense"
    if B is not None:
        kind, _ = ragged_mesh_plan(maybe_mesh(), B, S,
                                   E if E is not None else 1)
        if kind == "indivisible":
            return "dense"
    return "ragged"


def routing_drop_stats(logits: jax.Array, k: int, capacity_factor: float,
                       min_capacity: int = 4, ep: int = 1,
                       tokens_per_shard: Optional[int] = None
                       ) -> Dict[str, float]:
    """Dropped-token-choice fractions for both dispatch modes on one batch.

    ``dense``: per-EXPERT capacity C (GShard) — the fraction of the T*k
    choices that overflow an expert's capacity slots.
    ``ragged``: 0 off expert-parallel meshes (dropless by construction);
    under EP, the fraction overflowing a per-destination-SHARD buffer of
    :func:`ep_shard_capacity` slots, evaluated per token shard.
    """
    from deepspeed_tpu.moe.gating import gate_capacity, topk_gating

    T, E = logits.shape
    gate = topk_gating(logits, k=k, capacity_factor=capacity_factor,
                       min_capacity=min_capacity)
    kept = float(jnp.sum(gate.dispatch))
    dense_frac = 1.0 - kept / (T * k)

    ragged_frac = 0.0
    if ep > 1:
        t = tokens_per_shard or T
        idx = jnp.argsort(-logits, axis=-1)[:, :k]           # top-k experts
        dest = idx // (E // ep)                               # [T, k]
        Cs = ep_shard_capacity(t * k, ep)
        dropped = 0
        for s0 in range(0, T, t):
            d = dest[s0:s0 + t].reshape(-1)
            counts = jnp.bincount(d, length=ep)
            dropped += float(jnp.sum(jnp.maximum(counts - Cs, 0)))
        ragged_frac = dropped / (T * k)
    return {"dense": dense_frac, "ragged": ragged_frac,
            "dense_capacity": gate_capacity(T, E, k, capacity_factor,
                                            min_capacity)}


def _gate_indices(xt: jax.Array, gate_w: jax.Array,
                  gate_bias: Optional[jax.Array], k: int, score_func: str,
                  route_norm: bool, n_group: int, topk_group: int,
                  route_norm_eps: float = 0.0) -> IndexGateOutput:
    logits = xt.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    return topk_gating_indices(
        logits, k=k, normalize=route_norm, score_func=score_func,
        select_bias=gate_bias, n_group=n_group, topk_group=topk_group,
        normalize_eps=route_norm_eps)


def ep_shard_capacity(local_choices: int, ep: int) -> int:
    """Per-destination-shard buffer slots for the EP all-to-all.

    Balanced load is ``local_choices/ep``; 2× headroom makes shard-level
    drops rare (the shard buffer pools E/ep experts, so imbalance averages
    out — far coarser than the dense path's per-EXPERT capacity). Tiny
    inputs get a fully dropless buffer (the comm overhead is noise there).
    """
    return min(local_choices, max(64, -(-local_choices * 2 // ep)))


def _ragged_routed(x: jax.Array, gate_w: jax.Array,
                   experts: Dict[str, jax.Array],
                   gate_bias: Optional[jax.Array], *, activation: str, k: int,
                   score_func: str, route_norm: bool, n_group: int,
                   topk_group: int, route_norm_eps: float = 0.0,
                   first_expert: int = 0,
                   router_x: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Dropless routed-expert computation. Returns (y [B,S,H], aux, the
    call's :func:`held_meter` or None: a share's rows are counted where the
    call is one local program, not under a token-sharded mesh).
    ``first_expert``: where ``experts`` holds fewer experts than ``gate_w``
    has columns, the first of the contiguous ones held (:func:`moe_ffn`).
    ``router_x`` [B, S, .]: the rows the router scores, where they are not
    the rows ``x`` the experts take (a layer whose experts take a latent of
    the row, :func:`moe_ffn`'s ``latent``); one local program only.

    Three lowerings by mesh shape: single-shard sort+ragged_dot; per-shard
    sort inside ``shard_map`` when only token axes are sharded; and the
    expert-parallel fixed-capacity all-to-all (reference ``_AllToAll``
    ``sharded_moe.py:97`` — but with packed variable-occupancy buffers and a
    grouped matmul instead of [E,C,H] einsums).
    """
    B, S, H = x.shape
    E = gate_w.shape[1]
    mesh = maybe_mesh()

    kind, plan = ragged_mesh_plan(mesh, B, S, E)
    if kind == "shard" and router_x is not None:
        raise NotImplementedError(
            "experts that take a latent of the row (moe_latent_size) under "
            "a mesh that shards tokens or experts: the router's rows and "
            "the experts' are sharded as one array there")
    if kind != "shard":
        # 'local': nothing sharded (a pipe-only mesh never shards tokens or
        # experts). 'indivisible' (e.g. direct small-batch calls under a
        # lazily-initialized global mesh): shard_map is exact about shapes,
        # so run the plain local program and let GSPMD place it however the
        # inputs are actually sharded.
        xt = x.reshape(-1, H)
        with jax.named_scope("router"):
            gate = _gate_indices(
                xt if router_x is None
                else router_x.reshape(-1, router_x.shape[-1]), gate_w,
                gate_bias, k, score_func, route_norm, n_group, topk_group,
                route_norm_eps)
        meter = None
        with jax.named_scope("experts"):
            if experts["w_up"].shape[0] < E:
                y, rows = _held_routed(xt, gate.weights, gate.experts,
                                       experts, activation, first_expert, E)
                tiles = held_tiles(gate.experts.size, xt.shape[0])
                meter = held_meter(rows, gate.experts.size,
                                   tiles and tiles[0])
            else:
                y = _ragged_dispatch_local(xt, gate.weights, gate.experts,
                                           experts, activation)
        return y.reshape(B, S, H), gate.aux_loss, meter

    batch_axes, seq_ax, ep, tp = plan
    used_axes = set(batch_axes) | ({seq_ax} if seq_ax else set()) \
        | ({tp} if tp else set()) | ({EXPERT_AXIS} if ep > 1 else set())
    e_ax = EXPERT_AXIS if ep > 1 else None
    mean_axes = batch_axes + ((seq_ax,) if seq_ax else ())

    def _global_aux(gate: IndexGateOutput) -> jax.Array:
        """EXACT global-batch Switch aux under sharding: token-means of
        probs and first-choice mask are pmean'd BEFORE the dot product —
        identical to the dense path's estimator, not a mean of per-shard
        aux values (a product of means ≠ mean of products)."""
        me = jnp.mean(gate.probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(gate.experts[:, 0], E,
                                     dtype=jnp.float32), axis=0)
        if mean_axes:
            me = lax.pmean(me, mean_axes)
            ce = lax.pmean(ce, mean_axes)
        return jnp.sum(me * ce) * E

    bspec = P(batch_axes if batch_axes else None, seq_ax, None)
    espec = {kk: (P(e_ax, tp, None) if kk == "w_down" else P(e_ax, None, tp))
             for kk in experts}
    # bias of zeros ≡ no bias for SELECTION: argmax over gate_source+0 picks
    # the same experts as argmax over logits (softmax/sigmoid are monotone),
    # and combine weights never see the bias — keeps the in_specs pytree
    # uniform whether or not the model has e_score_correction_bias.
    gb = gate_bias if gate_bias is not None else jnp.zeros((E,), jnp.float32)

    if ep == 1:
        def local_fn(x_l, gw_l, ex_l, gb_l):
            b, s, _ = x_l.shape
            xt = x_l.reshape(-1, H)
            with jax.named_scope("router"):
                gate = _gate_indices(xt, gw_l, gb_l, k, score_func,
                                     route_norm, n_group, topk_group,
                                     route_norm_eps)
            with jax.named_scope("experts"):
                if ex_l["w_up"].shape[0] < E:
                    # a share, replicated over the token shards: each
                    # computes its own rows' pairs on the experts held
                    y, _ = _held_routed(xt, gate.weights, gate.experts,
                                        ex_l, activation, first_expert, E)
                else:
                    y = _ragged_dispatch_local(xt, gate.weights,
                                               gate.experts, ex_l, activation)
                if tp is not None:
                    y = lax.psum(y, tp)
            return y.reshape(b, s, H), _global_aux(gate), jnp.float32(0.0)
    else:
        if experts["w_up"].shape[0] < E:
            raise NotImplementedError(
                "a share of an expert layer (first_expert=) beside an "
                "`expert` mesh axis: the exchange between shares is not "
                f"written (mesh {dict(mesh.shape)})")
        if E % ep:
            raise ValueError(f"n_experts={E} not divisible by expert mesh axis {ep}")
        E_l = E // ep

        def local_fn(x_l, gw_l, ex_l, gb_l):
            b, s, _ = x_l.shape
            xt = x_l.reshape(-1, H)
            t = xt.shape[0]
            dt = xt.dtype
            gate = _gate_indices(xt, gw_l, gb_l, k, score_func, route_norm,
                                 n_group, topk_group, route_norm_eps)
            tk = t * k
            Cs = ep_shard_capacity(tk, ep)
            flat_e = gate.experts.reshape(tk)
            dest = flat_e // E_l                          # dest expert-shard
            # per-row slot in the packed send buffer, sort-free: position
            # within the destination's group via one-hot cumsum; overflow →
            # OOB sentinel (scatter drops it; the zero pad row on the way
            # back ⇒ dropped choice contributes 0, token falls through the
            # residual — dense-path drop semantics)
            onehot = jax.nn.one_hot(dest, ep, dtype=jnp.int32)
            pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                      dest[:, None], 1)[:, 0]
            slot = _ckpt_name(jnp.where(pos < Cs, dest * Cs + pos,
                                        ep * Cs).astype(jnp.int32), "moe_gate")
            if monitored:
                # global dropped-choice fraction across every source shard —
                # returned from the shard_map and reported via an async host
                # callback OUTSIDE it (debug callbacks don't lower inside a
                # partial-manual shard_map)
                ax = tuple(dict.fromkeys(
                    list(batch_axes) + ([seq_ax] if seq_ax else [])
                    + [EXPERT_AXIS]))
                drop_frac = (lax.psum(jnp.sum((slot == ep * Cs).astype(
                    jnp.float32)), ax) / lax.psum(jnp.float32(tk), ax))
            else:
                drop_frac = jnp.float32(0.0)
            # slot2row inverts slot (sentinel tk = empty buffer slot): both
            # buffer directions become pure gathers via buffer_exchange
            slot2row = _ckpt_name(
                jnp.full((ep * Cs,), tk, jnp.int32).at[slot].set(
                    jnp.arange(tk, dtype=jnp.int32), mode="drop"), "moe_gate")
            # k-duplication folded into the gather index (slot2row // k;
            # sentinel tk divides to t = xt's zero pad row) — the [tk, H]
            # broadcast copy is never materialized
            send_x = buffer_exchange_kdup(xt, slot2row // k,
                                          slot.reshape(t, k))
            send_e = jnp.where(
                slot2row < tk,
                jnp.take(flat_e % E_l, jnp.minimum(slot2row, tk - 1)),
                E_l)                                      # E_l = empty slot

            recv_x = lax.all_to_all(send_x.reshape(ep, Cs, H), EXPERT_AXIS,
                                    0, 0, tiled=True).reshape(ep * Cs, H)
            recv_e = lax.all_to_all(send_e.reshape(ep, Cs), EXPERT_AXIS,
                                    0, 0, tiled=True).reshape(ep * Cs)

            # counting sort by local expert; empties (sentinel E_l) land
            # past sum(group_sizes) — those rows are ZEROS under
            # lax.ragged_dot but UNDEFINED under the gmm path
            # (grouped_dot's contract): nothing below may read them — the
            # combine gathers strictly by `slot` (buffer_exchange), whose
            # sentinel hits the zero pad row, never a tail row of y_r
            ro, rinv, rc = expert_sort(recv_e, E_l + 1)
            ro = _ckpt_name(ro, "moe_gate")
            rinv = _ckpt_name(rinv, "moe_gate")
            rc = _ckpt_name(rc, "moe_gate")
            rx = permute_rows(recv_x, ro, rinv)
            y_r = ragged_expert_ffn(rx, rc[:E_l], ex_l, activation)
            if tp is not None:
                y_r = lax.psum(y_r, tp)                   # w_down F-sharded
            y_slots = permute_rows(y_r, rinv, ro).reshape(ep, Cs, H)

            y_back = lax.all_to_all(y_slots, EXPERT_AXIS, 0, 0,
                                    tiled=True).reshape(ep * Cs, H)
            # renormalize combine weights over the choices that SURVIVED the
            # buffer (dense-path semantics: denom runs over kept gates only)
            keep = (slot < ep * Cs).reshape(t, k).astype(jnp.float32)
            w = gate.weights * keep
            if route_norm:
                w = _normalized(w, jnp.sum(w, axis=1, keepdims=True),
                                route_norm_eps)
            contrib = buffer_exchange(y_back, slot, slot2row) * \
                w.reshape(tk)[:, None].astype(dt)
            y = contrib.reshape(t, k, H).sum(axis=1)
            return y.reshape(b, s, H), _global_aux(gate), drop_frac

    # manualize only the axes we use — nests under the pipeline's
    # axis_names={'pipe'} shard_map and leaves other axes to GSPMD. The
    # jit wrapper is inlined when already tracing (the normal engine path)
    # and makes eager calls legal (partial-manual out_specs are only
    # accepted under jit); it's cached so eager callers don't recompile
    # per invocation (jit caches on function identity).
    # under an ENCLOSING shard_map (compressed step manual over data/zshard,
    # pipeline manual over 'pipe') the nested shard_map must be built on the
    # context's abstract mesh — its axis_types record what is already manual
    sm_mesh = mesh
    if already_manual_axes():
        sm_mesh = jax.sharding.get_abstract_mesh()
    # trace-time: drop reporting is active only when a monitor is installed
    # AND we're not under an enclosing manual context (where the callback
    # can't lower) — gate BOTH the psums and the callback on it so the
    # unmonitored trace stays the zero-cost constant path
    monitored = (_DROP_MONITOR is not None and ep > 1
                 and not already_manual_axes())
    cache_key = (sm_mesh, k, activation, score_func, route_norm,
                 route_norm_eps, n_group, topk_group, first_expert, x.shape,
                 str(x.dtype),
                 gate_w.shape,
                 monitored,
                 tuple(sorted((kk, v.shape, str(v.dtype))
                              for kk, v in experts.items())))
    fn = _SHARDED_FN_CACHE.get(cache_key)
    if fn is None:
        fn = jax.jit(shard_map(local_fn, mesh=sm_mesh,
                               in_specs=(bspec, P(None, None), espec,
                                         P(None)),
                               out_specs=(bspec, P(), P()), check_vma=False,
                               axis_names=used_axes))
        if len(_SHARDED_FN_CACHE) >= 32:
            _SHARDED_FN_CACHE.pop(next(iter(_SHARDED_FN_CACHE)))
        _SHARDED_FN_CACHE[cache_key] = fn
    y, aux, drop_frac = fn(x, gate_w, experts, gb)
    if monitored:
        # async host report. Outside our shard_map; skipped under an
        # ENCLOSING manual context (compressed-collective step) where debug
        # callbacks can't lower — those runs still have routing_drop_stats.
        jax.debug.callback(_DROP_MONITOR, drop_frac)
    return y, aux, None


def moe_ffn(x: jax.Array, gate_w: jax.Array, experts: Dict[str, jax.Array],
            activation: str = "gelu", k: int = 2,
            capacity_factor: float = 1.25, min_capacity: int = 4,
            rng: Optional[jax.Array] = None, noise_std: float = 0.0,
            score_func: str = "softmax", route_norm: bool = True,
            route_scale: float = 1.0,
            shared: Optional[Dict[str, jax.Array]] = None,
            gate_bias: Optional[jax.Array] = None,
            n_group: int = 1, topk_group: int = 1,
            dispatch: str = "auto", route_norm_eps: float = 0.0,
            first_expert: int = 0, with_meter: bool = False,
            latent: Optional[Dict[str, jax.Array]] = None):
    """Mixture-of-experts FFN.

    x: [B, S, H]; gate_w: [H, E]; experts: w_up [E, H, F], w_down [E, F, H],
    optional w_gate [E, H, F] (swiglu). Returns (y [B,S,H], aux_loss scalar)
    and, ``with_meter``, a third: the call's :func:`held_meter` where it is
    a share that counts its rows, else None.

    ``dispatch``: 'auto' | 'ragged' (dropless sort + grouped matmul) |
    'dense' (capacity-factor GShard einsums) — see module docstring.

    Routing variants (AutoEP presets): ``score_func`` softmax|sigmoid,
    ``route_norm`` renormalizes top-k weights (``route_norm_eps``: what
    guards that division, ``gating._normalized``), ``route_scale`` scales the
    routed output (DeepSeek routed_scaling_factor). ``shared`` adds an
    always-on shared expert (sw_up [H,Fs], sw_down [Fs,H], optional sw_gate
    [H,Fs], optional shared_gate_w [H,1] sigmoid gate — Qwen2-MoE).

    A SHARE of the layer (expert parallelism's unit without its exchange),
    forward and backward: where ``experts`` holds fewer experts than
    ``gate_w`` has columns, they are the contiguous ones from
    ``first_expert``. The router scores and chooses over all of its
    columns, the pairs that fall here are computed
    (:func:`_held_routed`: always the dropless form, a row a pair), the
    others add nothing, forward or backward; the auxiliary loss is over the router's whole width, and gradients
    reach ``gate_w`` through the weights of the pairs here and through it.

    ``latent`` (``latent_down [H, l]``, ``latent_up [l, H]``): the routed
    experts take ``x W_down`` (their matrices are ``[E, l, F]`` / ``[E, F,
    l]``) and their weighted, scaled sum goes back up through ``W_up``;
    the router and the shared expert take the row itself. Always the
    dropless form.
    """
    B, S, H = x.shape
    dt = x.dtype
    T = B * S
    xt = x.reshape(T, H)

    held = experts["w_up"].shape[0] < gate_w.shape[1]
    mode = "ragged" if held or latent else resolve_dispatch(
        dispatch, rng, noise_std, B, S, gate_w.shape[1])
    meter = None
    if mode == "ragged":
        rows = x
        if latent:
            with jax.named_scope("latent_proj"):
                rows = x @ latent["latent_down"].astype(dt)
        y, aux, meter = _ragged_routed(
            rows, gate_w, experts, gate_bias, activation=activation, k=k,
            score_func=score_func, route_norm=route_norm, n_group=n_group,
            topk_group=topk_group, route_norm_eps=route_norm_eps,
            first_expert=first_expert, router_x=x if latent else None)
        y = y.reshape(T, -1)
    else:
        logits = xt.astype(jnp.float32) @ gate_w.astype(jnp.float32)   # [T, E]
        gate: GateOutput = topk_gating(
            logits, k=k, capacity_factor=capacity_factor,
            min_capacity=min_capacity, rng=rng, noise_std=noise_std,
            normalize=route_norm, score_func=score_func,
            select_bias=gate_bias, n_group=n_group, topk_group=topk_group,
            normalize_eps=route_norm_eps)
        aux = gate.aux_loss

        # dispatch: [T,E,C] × [T,H] → [E,C,H]; GSPMD turns the resharding of
        # the token dim (data/expert-sharded) onto the expert dim into an
        # all-to-all
        xe = jnp.einsum("tec,th->ech", gate.dispatch.astype(dt), xt)
        xe = _expert_constraint(xe)

        up = jnp.einsum("ech,ehf->ecf", xe, experts["w_up"].astype(dt))
        g = (jnp.einsum("ech,ehf->ecf", xe, experts["w_gate"].astype(dt))
             if "w_gate" in experts else None)
        act = _expert_act(up, g, activation)
        ye = jnp.einsum("ecf,efh->ech", act, experts["w_down"].astype(dt))
        ye = _expert_constraint(ye)

        y = jnp.einsum("tec,ech->th", gate.combine.astype(dt), ye)
    if route_scale != 1.0:
        y = y * jnp.asarray(route_scale, dt)
    if latent:
        with jax.named_scope("latent_proj"):
            y = y @ latent["latent_up"].astype(dt)
    if shared:
        y = y + _shared_experts(xt, shared, activation)
    y = y.reshape(B, S, H)
    return (y, aux, meter) if with_meter else (y, aux)


def _shared_experts(xt: jax.Array, shared: Dict[str, jax.Array],
                    activation: str) -> jax.Array:
    """The always-on experts beside the routed ones (:func:`moe_ffn`'s
    ``shared``) on flat rows ``xt [T, H]``."""
    with jax.named_scope("shared_experts"):
        ys = _dense_ffn(xt, shared["sw_up"], shared["sw_down"],
                        shared.get("sw_gate"), activation)
        if "shared_gate_w" in shared:
            sg = jax.nn.sigmoid(xt.astype(jnp.float32)
                                @ shared["shared_gate_w"].astype(jnp.float32))
            ys = ys * sg.astype(xt.dtype)
    return ys


def dropless_moe_ffn(xt: jax.Array, gate_w: jax.Array,
                     experts: Dict[str, jax.Array], activation: str, k: int,
                     score_func: str = "softmax", route_norm: bool = True,
                     route_scale: float = 1.0,
                     shared: Optional[Dict[str, jax.Array]] = None,
                     gate_bias: Optional[jax.Array] = None,
                     n_group: int = 1, topk_group: int = 1,
                     valid: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None,
                     first_expert: int = 0, route_norm_eps: float = 0.0,
                     latent: Optional[Dict[str, jax.Array]] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The serving form of :func:`moe_ffn` on flat local rows ``xt [T, H]``:
    always the dropless sort + grouped matmul, whatever ``moe_dispatch``
    says, because a served row's result must not depend on which rows
    share its tick (a capacity would drop by the tick's composition).
    Returns (y [T, H], rows per expert [E] int32 over the ``valid`` rows:
    a tick's pad rows route too and are left out of the count).
    ``layer``: ``experts`` are the layer stack's ``[L, E, ...]`` leaves and
    this is the layer to use (:func:`grouped_dot`).

    A SHARE of the layer (expert parallelism's unit without its exchange):
    where ``experts`` holds fewer experts than ``gate_w`` has columns, they
    are the contiguous ones from ``first_expert``. The router still scores
    and chooses over all of them, the pairs that fall here are computed,
    the others add nothing and cost no row of the grouped matmul
    (:func:`held_group_sizes`), and the shared expert runs for every row;
    the rows come back for every expert of the router.

    ``latent``: :func:`moe_ffn`'s (the router scores the row, the routed
    experts take its latent, their sum goes back up; both projections under
    the scope ``latent_proj``).

    The
    scopes ``router`` / ``experts`` / ``shared_experts`` are what a device
    trace sorts the layer's operations by."""
    with jax.named_scope("router"):
        gate = _gate_indices(xt, gate_w, gate_bias, k, score_func,
                             route_norm, n_group, topk_group, route_norm_eps)
        picked = jax.nn.one_hot(gate.experts, gate_w.shape[1],
                                dtype=jnp.int32)              # [T, k, E]
        if valid is not None:
            picked = picked * valid.astype(jnp.int32)[:, None, None]
        rows = jnp.sum(picked, axis=(0, 1))
    x_e = xt
    if latent:
        with jax.named_scope("latent_proj"):
            x_e = xt @ latent["latent_down"].astype(xt.dtype)
    with jax.named_scope("experts"):
        if experts["w_up"].shape[-3] < gate_w.shape[1]:
            y, _ = _held_routed(
                x_e, gate.weights, gate.experts, experts, activation,
                first_expert, gate_w.shape[1], layer)
        else:
            y = _ragged_dispatch_local(x_e, gate.weights, gate.experts,
                                       experts, activation, layer)
        if route_scale != 1.0:
            y = y * jnp.asarray(route_scale, xt.dtype)
    if latent:
        with jax.named_scope("latent_proj"):
            y = y @ latent["latent_up"].astype(xt.dtype)
    if shared:
        y = y + _shared_experts(xt, shared, activation)
    return y, rows
