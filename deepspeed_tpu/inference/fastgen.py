"""FastGen-class continuous batching: paged KV + Dynamic SplitFuse scheduling.

Parity: reference ``inference/v2/engine_v2.py`` (``put`` :107, ``query`` :158,
``flush`` :242 and the Dynamic SplitFuse policy in ``scheduling_utils.py:1-54``),
``inference/v2/ragged/blocked_allocator.py:1-105`` (block allocator) and
``ragged/kv_cache.py:1-208`` (blocked KV).

TPU design — one compiled program for EVERYTHING:

* KV lives in a block pool ``[L, NB, bs, K, D]``; each sequence owns a
  host-side block table (``BlockAllocator`` free list, block 0 = pad trash).
  A model that keeps per-sequence state beside its blocks (window rings,
  state-space state: ``models/paged.init_paged_kv``) also holds one of
  ``state_slots`` sequence SLOTS, acquired with its first block (the slot
  IS that block's id, so the device reads it from the table) and freed
  with it; admission waits for a slot as it waits for blocks.
* Every ``step()`` packs a fixed token budget T: one decode token per running
  sequence plus prefill CHUNKS of admitted prompts (long prompts split across
  ticks, short ones fused together — Dynamic SplitFuse), padded to T.
* The jitted tick (``models/paged.forward_paged``) embeds the flat tokens,
  writes K/V through the block tables, runs paged attention (Pallas kernel on
  TPU, XLA gather reference elsewhere) and samples every row; the host keeps
  only rows flagged as sequence heads. Admission NEVER recompiles — shapes are
  (T,), (T, MB) regardless of batch composition.

vs the v1 slot engine (``inference/ragged.py``): no per-sequence prefill
dispatch (admission is just host bookkeeping), no per-prompt-length compile
cache, prefill and decode share ticks so decode latency is bounded while
prompts stream in (the SplitFuse headline property).
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.models import paged as PG
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils.compile_cache import ensure_compile_cache

PyTree = Any

#: a step() tick's phases, in the order their boundaries are read
#: (``FastGenEngine._account_tick``); ``outside`` is what of a tick's
#: period lies before its ``schedule_tick``: the caller and the frontend
TICK_PHASES = ("schedule", "pack", "dispatch", "overlap", "readback",
               "commit")
_PERIOD_PARTS = ("outside",) + TICK_PHASES
#: a tick is slow when its period is this much over its program's typical
#: period, and by at least this many seconds
SLOW_TICK_RATIO, SLOW_TICK_MIN_S = 1.25, 1e-3
#: ticks of a program that only feed its typical values (a plain mean);
#: after them a mean over about the last ``_TYPICAL_SPAN`` ticks inside
#: the limit; a program slow this many ticks running is learned anew (its
#: contexts grew, the machine changed: slow is then the new typical)
_TYPICAL_WARMUP, _TYPICAL_SPAN, _SLOW_STREAK = 16, 32, 16
#: ticks between two refreshes of ``process_*`` (``telemetry/host.py``)
_PROCESS_REFRESH_TICKS = 16
#: edges of ``fastgen_tick_period_seconds``: a tenth apart from 2 to
#: 250 ms, so that a p99 is placed to within 5 %
_PERIOD_BUCKETS = (0.0005, 0.001) + tuple(
    round(0.002 * 1.1 ** i, 7) for i in range(52)) + (
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class BlockAllocator:
    """Fixed-pool block allocator (reference ``blocked_allocator.py:1-105``).

    Block 0 is reserved as the trash block pad tokens write into.

    ``state_slots`` > 0 (a model that keeps per-sequence state beside its
    blocks, ``models/paged.init_paged_kv``): blocks ``1 .. state_slots`` are
    HEAD blocks, handed out only as a sequence's first block, so a
    sequence's slot is its table's first entry and can be read from the
    table on the device. :meth:`allocate` starts a sequence (its first
    block is a head block), :meth:`grow` extends one; a head block is an
    ordinary block of the pool otherwise (it holds the sequence's first
    positions). With no slots the two are one free list and the same
    call."""

    def __init__(self, n_blocks: int, state_slots: int = 0):
        if not 0 <= state_slots < n_blocks:
            raise ValueError(f"state_slots={state_slots} of {n_blocks} blocks")
        self.n_blocks, self.state_slots = n_blocks, state_slots
        # blocks leave at the front and come back at the back: the free
        # list is as long as the pool, and a list moves all of it to give
        # up its first entry
        self._heads = collections.deque(range(1, state_slots + 1))
        self._free = collections.deque(range(state_slots + 1, n_blocks))
        # (queue, block, taken) of every block moved since begin(), or None
        self._journal: Optional[List[tuple]] = None

    @property
    def free_blocks(self) -> int:
        """Blocks not held by a sequence, head blocks included."""
        return len(self._free) + len(self._heads)

    @property
    def free_slots(self) -> int:
        return len(self._heads)

    @property
    def slots_in_use(self) -> int:
        return self.state_slots - len(self._heads)

    def available(self, starting: bool) -> int:
        """Blocks a sequence could be given now: one that ``starting``
        (it holds none yet) needs a head block for its first."""
        if not self.state_slots or not starting:
            return len(self._free)
        return 1 + len(self._free) if self._heads else 0

    def _take(self, queue: collections.deque, n: int) -> List[int]:
        out = [queue.popleft() for _ in range(n)]
        if self._journal is not None:
            self._journal.extend((queue, b, True) for b in out)
        return out

    def allocate(self, n: int = 1) -> List[int]:
        """The ``n`` blocks of a NEW sequence."""
        if not self.state_slots or n < 1:
            return self.grow(n)
        if not self._heads:
            raise RuntimeError(
                f"no sequence slot free ({self.state_slots} in use)")
        rest = self.grow(n - 1)
        return self._take(self._heads, 1) + rest

    def grow(self, n: int = 1) -> List[int]:
        """``n`` more blocks for a sequence that has its first."""
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: want {n} blocks, {len(self._free)} free")
        return self._take(self._free, n)

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if b:
                queue = self._heads if b <= self.state_slots else self._free
                queue.append(b)
                if self._journal is not None:
                    self._journal.append((queue, b, False))

    def begin(self) -> None:
        """Remember every block that moves from here on, for
        :meth:`rollback`: a tick takes a few blocks off the front and
        appends a finished sequence's at the back, so what it did is a few
        entries where a copy of the free list is the whole pool."""
        self._journal = []

    def commit(self) -> None:
        self._journal = None

    def rollback(self) -> None:
        """Both lists as they were at :meth:`begin`, order included."""
        for queue, b, taken in reversed(self._journal or ()):
            if taken:
                queue.appendleft(b)
            else:
                queue.pop()
        self._journal = None

    def snapshot(self) -> tuple:
        """Both lists, in order (a reader for tests: a tick's roll-back is
        :meth:`begin` / :meth:`rollback`)."""
        return list(self._free), list(self._heads)


class _Rows:
    """The scheduler's state of every sequence, a row a sequence, in arrays
    the engine owns: a phase of a tick is one pass over them, and a
    sequence meets per-row Python only in a tick in which something
    happens to it (``FastGenEngine._step_impl``).

    A sequence holds a row from ``put()`` to ``flush()``
    (:meth:`acquire` / :meth:`release`); ``_Seq`` reads and writes its row
    through properties. A released row is the next one handed out, so
    the rows ever used are ``[:hi]`` and ``hi`` is the most sequences the
    engine has held at once.

    A field a tick changes is in ``TICK_FIELDS``: ``step()``'s roll-back
    copies those (``snapshot`` / ``restore``), and a new one that is not
    listed there is not rolled back."""

    #: what ``step()`` may change between its snapshot and its return
    TICK_FIELDS = ("pos", "prefilled", "last_tok", "live", "held",
                   "first_seen", "gen_len", "table")

    def __init__(self, capacity: int, max_blocks: int):
        self.capacity = capacity
        self.pos = np.zeros(capacity, np.int32)        # tokens in cache
        self.prefilled = np.zeros(capacity, np.int32)  # prompt, written
        self.prompt_len = np.zeros(capacity, np.int32)
        self.last_tok = np.full(capacity, -1, np.int32)  # -1: none yet
        self.live = np.zeros(capacity, bool)    # admitted and not done
        self.held = np.zeros(capacity, np.int32)       # blocks held
        self.first_seen = np.zeros(capacity, bool)     # TTFT observed
        self.gen_len = np.zeros(capacity, np.int32)    # len(generated)
        self.deadline = np.full(capacity, np.inf)      # perf_counter clock
        self.uid = np.empty(capacity, object)
        # the block tables, ``table[r, :held[r]]`` a sequence's blocks in
        # order and zeros (the trash block) behind them
        self.table = np.zeros((capacity, max_blocks), np.int32)
        self.seqs: List[Optional["_Seq"]] = [None] * capacity
        self.free: List[int] = []     # released rows below ``hi``
        self.hi = 0

    def acquire(self, seq: "_Seq") -> int:
        if self.free:
            r = self.free.pop()
        else:
            if self.hi == self.capacity:
                self._widen()
            r, self.hi = self.hi, self.hi + 1
        self.seqs[r] = seq
        self.uid[r] = seq.uid
        self.prompt_len[r] = len(seq.prompt)
        self.deadline[r] = np.inf if seq.deadline is None else seq.deadline
        self.live[r] = True
        return r

    def release(self, seq: "_Seq") -> None:
        """Give ``seq``'s row back. The descriptor keeps ``generated``,
        ``done`` and ``expired`` and loses its row: ``flush`` has taken it
        out of ``seqs``, where ``query``, ``rematerialize`` and the
        frontend find a descriptor, so nothing reads its row again."""
        r = seq.row
        seq._st = seq.row = None
        for name in self.TICK_FIELDS:
            getattr(self, name)[r] = 0
        self.last_tok[r] = -1
        self.uid[r] = self.seqs[r] = None
        self.free.append(r)

    def _widen(self) -> None:
        wider = _Rows(2 * self.capacity, self.table.shape[1])
        for name, arr in vars(self).items():
            if isinstance(arr, np.ndarray):
                getattr(wider, name)[:self.capacity] = arr
        wider.seqs[:self.capacity] = self.seqs
        wider.free, wider.hi = self.free, self.hi
        self.__dict__ = wider.__dict__

    def snapshot(self) -> tuple:
        n = self.hi
        return tuple(getattr(self, name)[:n].copy()
                     for name in self.TICK_FIELDS)

    def restore(self, snap: tuple) -> None:
        """The rows as :meth:`snapshot` saw them, and what of a sequence
        lives on its descriptor with them: ``done`` and the tokens kept."""
        for name, was in zip(self.TICK_FIELDS, snap):
            getattr(self, name)[:len(was)] = was
        for seq in self.seqs[:len(snap[0])]:
            if seq is not None:
                seq.done = not self.live.item(seq.row)
                del seq.generated[self.gen_len[seq.row]:]


class _Seq:
    """Host-side descriptor (reference ``sequence_descriptor.py``).

    What the scheduler changes tick by tick (``pos``, ``prefilled``,
    ``last_tok``, the blocks and their table, whether the first token was
    seen) lives in the engine's ``_Rows``, in this sequence's ``row``, and
    is read here through properties; ``generated`` is a plain list and
    ``done`` / ``expired`` plain attributes, mirrored by ``_Rows.live``
    and ``_Rows.gen_len`` (``_finish`` and a tick's commit keep
    both). A new field the scheduler mutates goes into
    ``_Rows`` and its ``TICK_FIELDS``, or ``step()`` does not roll it
    back."""

    def __init__(self, uid: int, prompt: List[int], rows: _Rows,
                 deadline_s: Optional[float] = None):
        self.uid = uid
        self.prompt = prompt
        self.generated: List[int] = []
        self.done = False
        self.admit_t = time.perf_counter()    # TTFT anchor (telemetry)
        # absolute expiry (perf_counter clock); None = no deadline
        self.deadline = (self.admit_t + deadline_s
                         if deadline_s is not None else None)
        self.expired = False
        self.slot_waited = False      # counted in state_slot_waits_total
        self._st = rows
        self.row = rows.acquire(self)

    @property
    def pos(self) -> int:
        """Total tokens in cache."""
        return self._st.pos.item(self.row)

    @pos.setter
    def pos(self, value: int) -> None:
        self._st.pos[self.row] = value

    @property
    def prefilled(self) -> int:
        """Prompt tokens written to cache."""
        return self._st.prefilled.item(self.row)

    @prefilled.setter
    def prefilled(self, value: int) -> None:
        self._st.prefilled[self.row] = value

    @property
    def prefill_remaining(self) -> int:
        return len(self.prompt) - self._st.prefilled.item(self.row)

    @property
    def last_tok(self) -> Optional[int]:
        """The next decode input; None before the first sampled token."""
        tok = self._st.last_tok.item(self.row)
        return tok if tok >= 0 else None

    @last_tok.setter
    def last_tok(self, value: Optional[int]) -> None:
        self._st.last_tok[self.row] = -1 if value is None else value

    @property
    def first_tok_seen(self) -> bool:
        return self._st.first_seen.item(self.row)

    @first_tok_seen.setter
    def first_tok_seen(self, value: bool) -> None:
        self._st.first_seen[self.row] = value

    @property
    def held(self) -> int:
        """Blocks held."""
        return self._st.held.item(self.row)

    @property
    def table(self) -> np.ndarray:
        """The block table: a view of the engine's row."""
        return self._st.table[self.row]

    @property
    def blocks(self) -> List[int]:
        """The blocks held, in order (a copy: ``table`` is the store)."""
        return self._st.table[self.row, :self.held].tolist()


class FastGenEngine:
    """``put/query/flush`` continuous-batching engine (engine_v2 analog)."""

    #: the last 128 slow step() ticks (``_account_tick``), oldest first:
    #: one record a tick with its number (the ``decode_tick`` span's
    #: ``tick``), program, period, typical period and every part of both.
    #: Process-wide like the registry its counters live in, so that a
    #: reader needs no handle on an engine that may be gone; a record's
    #: ``engine`` is the ``engine_no`` of the engine that made it
    slow_ticks: collections.deque = collections.deque(maxlen=128)
    _engine_numbers = itertools.count()

    # what this process traces, lowers, loads and compiles from here on is
    # accounted by program (telemetry/host.py); the constructor is the span
    # ``engine_init`` and the parts of it that a replica's cold start is
    # suspected of are spans inside it
    @telemetry.engine_init()
    def __init__(self, cfg: Union[str, T.TransformerConfig],
                 params: Optional[PyTree] = None,
                 n_blocks: int = 128, block_size: int = 32,
                 max_blocks_per_seq: int = 16, token_budget: int = 64,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 use_pallas_kernel: Optional[bool] = None,
                 tp: Optional[bool] = None,
                 request_deadline_s: Optional[float] = None,
                 state_slots: Optional[int] = None, **overrides):
        ensure_compile_cache()
        if isinstance(cfg, str):
            cfg = T.get_model_config(cfg, **overrides)
        self.cfg = cfg
        # the first touch of the devices in a process that had none: the
        # runtime's start, seconds on a TPU host
        with telemetry.span("device_attach"):
            jax.devices()
        with telemetry.span("params_init"):
            if params is None:
                params = T.init_params(cfg, jax.random.PRNGKey(seed))
            self.params = jax.tree.map(
                lambda x: jnp.asarray(x, cfg.compute_dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                else jnp.asarray(x), params)
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.token_budget = token_budget
        # cap at the model's position range: learned pos-emb gathers clamp
        # silently out of range, so never let sequences grow past it
        self.max_len = min(block_size * max_blocks_per_seq, cfg.max_seq_len)
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        self.eos_token_id = eos_token_id
        # default per-request deadline (seconds from admission; None = no
        # deadline): expired requests are dropped at the next scheduling
        # tick so one stuck/abandoned client can't pin KV blocks and
        # queue slots forever. put() can override per request.
        self.request_deadline_s = request_deadline_s

        # sequence slots: what a model of ``layer_kinds`` keeps per
        # sequence beside its blocks (rings, recurrent and convolution
        # state) lives in one
        # of ``state_slots`` rows, acquired with a sequence's first block
        # and freed with it; 0 for every other model. The default holds a
        # small tick bucket's decode rows, or as many as take no more
        # memory than the blocks do (a slot of delta-rule state is
        # megabytes: ``_pool_bytes``).
        def pool_bytes(slots):
            return self._pool_bytes(cfg, n_blocks, block_size, slots,
                                    token_budget)

        if not cfg.layer_kinds:
            state_slots = 0
        elif state_slots is None:
            state_slots = max(1, min(token_budget // 8, (n_blocks - 1) // 2))
            blocks, one = pool_bytes(1)
            per_slot = pool_bytes(2)[1] - one
            state_slots = max(1, min(state_slots, int(
                max(blocks, 256 << 20) // max(per_slot, 1))))
        # a pool of blocks alone whose calls take a table a sequence: the
        # tick counts its sequences itself, and has room for so many tables
        most = PG.tick_tables(token_budget, max_blocks_per_seq)
        if token_budget > most <= state_slots and all(
                s.cls == PG.BLOCKS for _, s in PG.pool_stores(cfg)):
            raise ValueError(
                f"state_slots={state_slots}: a tick of {token_budget} rows "
                f"with tables of {max_blocks_per_seq} blocks holds {most} "
                "sequences, its pad rows' among them")
        # reckoned before it is built: a pool that cannot fit says so here
        # and not as an allocation failure in the middle of a tick
        blocks, state = pool_bytes(state_slots)
        # what a layer of a kind materialises inside the largest tick
        # (a sparse layer's scores of a chunk: 2,048 rows x 18k positions)
        tick = max((kind.tick_bytes(token_budget,
                                    block_size * max_blocks_per_seq)
                    for kind in PG.cache_kinds(cfg).values()
                    if kind.tick_bytes is not None), default=0)
        stats = jax.devices()[0].memory_stats() or {}
        weights = sum(x.nbytes for x in jax.tree.leaves(self.params))
        if stats.get("bytes_limit") and \
                weights + blocks + state + tick > stats["bytes_limit"]:
            raise ValueError(
                f"the pool does not fit the device: {n_blocks} blocks of "
                f"{block_size} take {blocks / 1e9:.2f} GB and {state_slots} "
                f"sequence slots' state {state / 1e9:.2f} GB beside "
                f"{weights / 1e9:.2f} GB of weights"
                + (f" and {tick / 1e9:.2f} GB a tick holds" if tick else "")
                + f", of {stats['bytes_limit'] / 1e9:.2f} GB")
        self.allocator = BlockAllocator(n_blocks, state_slots)
        with telemetry.span("state_init"):
            self.pool = PG.init_paged_kv(cfg, n_blocks, block_size,
                                         state_slots=state_slots,
                                         max_run=token_budget)
        self.seqs: Dict[int, _Seq] = {}
        # the scheduler's state, a row a sequence (``_Rows``), and the
        # live and finished-but-unflushed sequences in admission order:
        # their uids, and their rows as an array (None: stale)
        self._rows = _Rows(256, max_blocks_per_seq)
        self._admit_order: List[int] = []
        self._order: Optional[np.ndarray] = None
        self._decode_rr = 0
        self._ticks_run = 0     # step() ticks dispatched, for span attributes
        self.engine_no = next(FastGenEngine._engine_numbers)
        # HOST-side key stream: deriving per-call subkeys with an eager
        # jax.random.split is a whole device dispatch for an 8-byte op. Any
        # uint32[2] is a valid raw threefry key, so a host PCG stream
        # supplies them (two words of a tick's packed array).
        self._host_rng = np.random.default_rng(seed)
        self._ticks: Dict[int, Any] = {}   # bucketed by tick token count
        self._setup_telemetry()

        # --- TP serving (round-4 verdict Missing #5: "eventually served
        # TP>1"): when a live mesh has a non-trivial 'tensor' axis, params
        # take the AutoTP shardings (same rules as the v1 engine,
        # inference/engine.py) and the paged pool shards its kv-heads dim;
        # GSPMD inserts the row/col-parallel collectives in every tick
        # program. Host-side scheduling (blocks, SplitFuse plan) is
        # unchanged — it never touches device layouts.
        self.mesh = None
        self._rep_sh = None
        if tp is not False:
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS, maybe_mesh

            _m = maybe_mesh()
            if _m is not None and _m.shape.get(TENSOR_AXIS, 1) > 1:
                self.mesh = _m
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from deepspeed_tpu.comm.mesh import TENSOR_AXIS
            from deepspeed_tpu.parallel.partitioning import ShardingPolicy

            tp_size = self.mesh.shape[TENSOR_AXIS]
            # incompatibilities: with tp=None (auto) fall back to the old
            # replicated serving with a warning — a live training mesh must
            # not brick an eval engine; tp=True makes them hard errors
            problem = None
            if cfg.mla:
                problem = ("MLA latent-KV pools are per-head-free and not "
                           "sharded yet — serve MLA models single-replica")
            elif cfg.layer_kinds:
                problem = ("rings and per-sequence state are not sharded "
                           "yet — serve a stack of layer kinds "
                           "single-replica")
            elif cfg.kv_heads % tp_size != 0:
                problem = (f"kv_heads {cfg.kv_heads} not divisible by "
                           f"tensor axis {tp_size}")
            elif use_pallas_kernel:
                problem = ("the Pallas paged-attention kernel is not "
                           "shard_map-wrapped — TP serving uses the XLA "
                           "attention path (use_pallas_kernel=False)")
            if problem is not None:
                if tp:
                    raise NotImplementedError(f"FastGen TP: {problem}")
                import warnings

                warnings.warn(f"FastGen TP disabled ({problem}); serving "
                              "replicated")
                self.mesh = None
            else:
                policy = ShardingPolicy(self.mesh, zero_stage=0)
                sh = policy.to_shardings(
                    policy.tp_spec(T.param_logical_axes(cfg)))
                self.params = jax.tree.map(jax.device_put, self.params, sh)
                pool_sh = NamedSharding(
                    self.mesh, P(None, None, None, TENSOR_AXIS, None))
                self.pool = jax.tree.map(
                    lambda x: jax.device_put(x, pool_sh), self.pool)
                self._rep_sh = NamedSharding(self.mesh, P())
                use_pallas_kernel = False
        if use_pallas_kernel is None:
            use_pallas_kernel = jax.default_backend() == "tpu"
        self._use_kernel = use_pallas_kernel
        # what every tick's attention is, and the rows of its kernel tile
        # (0 where it is not a kernel)
        self._attention, self._tile_rows = PG.tick_attention(
            cfg, use_pallas_kernel)
        # (layers, window, positions a fetch step) of the kernel's calls
        # in a tick: what the count of a tick's fetch steps needs
        self._walks = PG.tick_walks(cfg, self.pool) if self._tile_rows \
            else []
        # what a tick of a given shape does to each kind of layer's state,
        # by the kind's own rule: attributes of the tick's span
        self._kind_spans = [kind.span for kind in PG.cache_kinds(cfg).values()
                            if kind.span is not None]
        # expert layers whose per-expert row counts ride back with a
        # tick's sampled tokens; 0 for a model without experts
        self._expert_layers = sum(
            c.ffn_layers for _, c in cfg.segments if c.n_experts)
        # a looped stack (``cfg.loop_passes`` > 1): what its ticks say of
        # themselves on their span (its rows' exit distribution rides back
        # with a tick's sampled tokens); nothing for any other model
        self._loop_attrs = {
            "loop_passes": cfg.loop_passes,
            "cache_layers": cfg.loop_passes * cfg.num_layers} \
            if cfg.loop_passes > 1 else {}

    @classmethod
    def _pool_bytes(cls, cfg, n_blocks: int, block_size: int,
                    state_slots: int, max_run: int) -> Tuple[int, int]:
        """(bytes of the block stores, bytes of the per-slot state stores)
        of the pool ``init_paged_kv`` would build, without building it:
        which store is which is its class in the model's table of cache
        kinds (``paged.store_bytes``)."""
        held = PG.store_bytes(cfg, jax.eval_shape(lambda: PG.init_paged_kv(
            cfg, n_blocks, block_size, state_slots=state_slots,
            max_run=max_run)))
        blocks = sum(n for s, n in held if s.cls == PG.BLOCKS)
        return blocks, sum(n for _, n in held) - blocks

    # ------------------------------------------------------------------ #
    # telemetry (README "Observability" — fastgen_* metric catalog)
    # ------------------------------------------------------------------ #
    def _setup_telemetry(self) -> None:
        """Serving metrics on the process-wide registry. Hot-path cost per
        tick is a handful of dict updates plus an O(live-sequences) gauge
        sweep — noise against a device dispatch; nothing here fences."""
        self._tm_ttft = telemetry.histogram(
            "fastgen_ttft_seconds",
            "admission (put) to first generated token, host-observed")
        self._tm_tok_lat = telemetry.histogram(
            "fastgen_decode_token_seconds",
            "per-token decode latency (window wall time / tokens)")
        # per-ENGINE accumulators behind est_token_seconds: the histogram
        # above is process-global, so two engines in one process (draft +
        # large model) would blend into one lifetime mean there
        self._tok_lat_sum = 0.0
        self._tok_lat_n = 0
        # sliding-window twin (ring of (interval, sum, n) over ~60s):
        # est_token_seconds prefers the windowed mean so one slow warmup
        # tick can't skew routing scores and retry-after hints forever
        self._tok_lat_win: collections.deque = collections.deque()
        self._tok_lat_win_interval_s = 10.0
        self._tok_lat_win_intervals = 6
        self._tm_ticks = telemetry.counter(
            "fastgen_ticks_total",
            "engine ticks by kind (mixed: the tick held prompt rows / "
            "decode: it held none) and "
            "block-table width tier")
        self._tm_h2d = telemetry.counter(
            "fastgen_tick_h2d_bytes_total",
            "bytes of the one host array a step() tick hands to its "
            "program: tokens, positions, block tables, the sampled rows of "
            "a full-budget tick and the key words")
        self._tm_gen_tok = telemetry.counter(
            "fastgen_generated_tokens_total", "tokens sampled and kept")
        self._tm_prefill_tok = telemetry.counter(
            "fastgen_prefill_tokens_total",
            "prompt tokens written into the KV cache")
        self._tm_head_rows = telemetry.counter(
            "fastgen_head_rows_total",
            "rows the head (final norm, vocabulary matmul, sampling) ran "
            "for in step() ticks, by form: gathered (the sampled rows of a "
            "full-budget tick, the small bucket's row count a tick) / all "
            "(every row of the bucket)")
        self._tm_shared_rows = telemetry.counter(
            "fastgen_paged_shared_rows_total",
            "prompt rows of step() ticks that sat in a kernel tile wholly "
            "inside one chunk (their tile walked the sequence's blocks "
            "once); over fastgen_prefill_tokens_total: the hit share")
        self._tm_attn_steps = telemetry.counter(
            "fastgen_attention_steps_total",
            "fetch steps the paged-attention kernel's calls of step() "
            "ticks walked, by form: open (every column live for every row "
            "of the step: computed without a mask) / masked")
        self._tm_expert_imbalance = telemetry.histogram(
            "fastgen_expert_load_imbalance",
            "step() ticks of an expert model, by tick bucket: the busiest "
            "expert's rows over the mean rows an expert got, each summed "
            "over the expert layers (1 = even routing)",
            buckets=(1.0, 1.25, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0,
                     12.0, 16.0, 32.0, 64.0))
        self._tm_expert_pairs = telemetry.counter(
            "fastgen_expert_pairs_total",
            "(row, expert) pairs the routers of step() ticks chose over "
            "the ticks' real rows and the expert layers, by whether the "
            "expert is held here (held=no: a share of the experts, "
            "TransformerConfig.moe_router_experts; the pair adds nothing "
            "and costs no row)")
        self._tm_held_rows = telemetry.histogram(
            "fastgen_held_expert_rows",
            "step() ticks of an expert model, by tick bucket: mean rows a "
            "held expert got (pairs on held experts over the experts held, "
            "a layer)",
            buckets=(0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
                     128.0, 256.0, 512.0))
        self._tm_tick_rows = telemetry.counter(
            "fastgen_tick_rows_total",
            "sequences a step() tick considered (a decode row, a prompt "
            "chunk, a row that waited for the pool), by path: python (it "
            "took per-row Python: grew a block or waited for one, was a "
            "chunk's, saw its first token, finished) / array (the passes "
            "over the scheduler's arrays alone)")
        self._tick_rows_keys = (telemetry.label_key(path="array"),
                                telemetry.label_key(path="python"))
        self._tm_preempt = telemetry.counter(
            "fastgen_preemptions_total",
            "sequences deferred a tick by KV-pool backpressure")
        self._tm_deadline = telemetry.counter(
            "fastgen_deadline_expired_total",
            "requests dropped past their deadline, by state at expiry "
            "(waiting=still prefilling, running=decoding)")
        self._tm_evict = telemetry.counter(
            "fastgen_evicted_blocks_total",
            "KV blocks released at sequence finish/flush")
        self._tm_finished = telemetry.counter(
            "fastgen_sequences_finished_total", "sequences that completed")
        self._tm_queue = telemetry.gauge(
            "fastgen_queue_depth",
            "live sequences by state (waiting=prefill pending, "
            "running=decoding)")
        self._tm_queue_peak = telemetry.gauge(
            "fastgen_queue_depth_peak", "high-water mark of live sequences")
        self._tm_occup = telemetry.gauge(
            "fastgen_batch_occupancy",
            "fraction of the tick's rows carrying real work")
        self._tm_kv = telemetry.gauge(
            "fastgen_kv_pool_utilization",
            "fraction of the KV block pool allocated")
        self._tm_kv_peak = telemetry.gauge(
            "fastgen_kv_pool_utilization_peak",
            "high-water mark of KV pool utilization")
        self._tm_slots = telemetry.gauge(
            "fastgen_state_slots_in_use",
            "sequence slots (rings and recurrent state of a model that "
            "keeps them) held by live sequences; 0 for other models")
        self._tm_slots_peak = telemetry.gauge(
            "fastgen_state_slots_in_use_peak",
            "high-water mark of fastgen_state_slots_in_use")
        self._tm_slot_waits = telemetry.counter(
            "fastgen_state_slot_waits_total",
            "admitted sequences whose first prompt chunk waited a tick or "
            "more for a sequence slot")
        self._tm_kv_tier = telemetry.gauge(
            "fastgen_kv_blocks_in_use",
            "allocated KV blocks bucketed by the owning sequence's "
            "block-table width tier (quarter/half/full)")
        # the account of a step() tick's period (``_account_tick``)
        self._tm_period = telemetry.histogram(
            "fastgen_tick_period_seconds",
            "end of the previous step() tick's tick_commit to the end of "
            "this one's while the engine held a live sequence (after an "
            "idle stretch: from this tick's schedule_tick), by the tick's "
            "kind and row bucket",
            buckets=_PERIOD_BUCKETS)
        self._tm_phase = telemetry.counter(
            "fastgen_tick_phase_seconds_total",
            "seconds of step() ticks by phase (schedule / pack / dispatch "
            "/ overlap / readback / commit: consecutive clock readings, so "
            "a tick's phases sum to its schedule_tick entry to tick_commit "
            "exit) and kind")
        self._tm_idle = telemetry.counter(
            "fastgen_engine_idle_seconds_total",
            "seconds between two step() ticks during which the engine "
            "held no live sequence: in no tick's period")
        self._tm_slow = telemetry.counter(
            "fastgen_slow_ticks_total",
            "step() ticks whose period was over 1.25 x and 1 ms over their "
            "program's typical period, by the phase (or outside: caller "
            "and frontend) whose excess over its own typical was largest")
        self._tm_slow_excess = telemetry.counter(
            "fastgen_slow_tick_excess_seconds_total",
            "seconds by which slow ticks' periods passed their programs' "
            "typical period, by the phase that owned the tick")
        # what a sequence slot holds beside its blocks, by kind of state
        # (``models/paged.init_paged_kv``): constants of the engine
        state_bytes = telemetry.gauge(
            "fastgen_state_bytes_per_slot",
            "bytes a sequence slot holds whatever its sequence's length, by "
            "kind: conv (a convolution's last inputs) / ring (window "
            "layers' keys and values) / scan (a recurrence's matrix) / "
            "rule (a delta rule's matrices) / ssd (a state-space duality "
            "layer's matrices, a head each)")
        by_kind: Dict[str, int] = {}
        for s, n in PG.store_bytes(self.cfg, self.pool):
            if s.cls != PG.BLOCKS:
                by_kind[s.holds] = by_kind.get(s.holds, 0) + n
        for kind, n in by_kind.items():
            state_bytes.set(n / (self.allocator.state_slots + 1), kind=kind)
        telemetry.gauge(
            "fastgen_state_bytes",
            "bytes of the stores that hold a row a sequence slot (rings, "
            "convolution, recurrence and delta-rule state), all slots"
        ).set(sum(by_kind.values()))
        self._tm_kda_rows = telemetry.counter(
            "fastgen_kda_rows_total",
            "rows of ticks through the delta-rule layers, by the form of "
            "the rule that took them: step (runs of one row: one read and "
            "one write of the sequence's state) / chunk (chunkwise)")
        self._tm_kda_pieces = telemetry.counter(
            "fastgen_kda_chunk_pieces_total",
            "grid steps of the delta rule's chunk form that did work: the "
            "chunks of 64 rows of a tick that a run it takes has a row in")
        self._tm_ssd_rows = telemetry.counter(
            "fastgen_ssd_rows_total",
            "rows of ticks through the mamba2 layers, by the form of the "
            "recurrence that took them: step (runs of one row: one read "
            "and one write of the sequence's state) / chunk (chunked)")
        self._tm_index_positions = telemetry.counter(
            "fastgen_index_positions_total",
            "cache positions the sparse layers' indexers scored: the "
            "lengths of the ticks' real rows, summed over the layers")
        self._tm_sparse_selected = telemetry.counter(
            "fastgen_sparse_selected_total",
            "cache positions the rows of sparse layers attended to: "
            "min(length, topk) a row, summed over the layers")
        self._tm_sparse_read = telemetry.counter(
            "fastgen_sparse_positions_read_total",
            "cache positions the sparse layers' attention met for those "
            "rows: a row walks its sequence with the choice as a mask, so "
            "its length, summed over the layers")
        self._tm_layer_apps = telemetry.counter(
            "fastgen_layer_applications_total",
            "layer applications of step() ticks of a looped stack "
            "(TransformerConfig.loop_passes): ticks x passes x layers, each "
            "a walk of a cache layer of its own")
        self._tm_exit_mass = telemetry.counter(
            "fastgen_exit_mass_total",
            "a looped stack's exit distribution summed over the rows whose "
            "token step() ticks read, by pass: over the rows, the share of "
            "tokens an exit threshold would release at each pass")
        telemetry.gauge(
            "fastgen_cache_layers",
            "layers of the pool's block stores: the stack's layers that "
            "keep keys and values, times the passes of a looped stack"
        ).set(max((layers for layers, s in PG.pool_stores(self.cfg)
                   if s.cls == PG.BLOCKS), default=0))
        self._period_keys: Dict[tuple, tuple] = {}   # (kind, Tn) -> keys
        # the last step() tick's end (None before the first), and whether
        # the engine has been without a live sequence since
        self._tick_end_t: Optional[float] = None
        self._idle = False
        self._gc_seen_s = telemetry.gc_pause_seconds()
        self._compile_seen_s = telemetry.compile_seconds()
        # (tick end, process CPU seconds, this thread's) when the CPU
        # clocks were last read: every sixteenth tick and at a slow one
        # (two system calls: not every tick's to pay)
        self._cpu_seen = (time.perf_counter(), time.process_time(),
                          time.thread_time())
        # program -> [ticks fed, slow ticks running, typical seconds of
        # each of _PERIOD_PARTS]
        self._typical: Dict[tuple, List[float]] = {}
        self._tracer = telemetry.get_tracer()
        telemetry.install_gc_span()

    def _mb_tier_name(self, mb: int) -> str:
        """Label for a table width, derived from the SAME bounds as
        _mb_tier so the metric labels can never drift from the actual
        compile-cache tiers."""
        quarter, half = self._mb_tier_bounds()
        return "quarter" if mb <= quarter else \
            "half" if mb <= half else "full"

    def _tm_sched_gauges(self) -> None:
        """Refresh queue/pool gauges from host scheduler state."""
        st = self._rows
        live, held = st.live[:st.hi], st.held[:st.hi]
        n_live = int(np.count_nonzero(live))
        if not n_live:
            self._idle = True       # until the next tick: no one waits
        waiting = int(np.count_nonzero(
            live & (st.prefilled[:st.hi] < st.prompt_len[:st.hi])))
        self._tm_queue.set(waiting, state="waiting")
        self._tm_queue.set(n_live - waiting, state="running")
        self._tm_queue_peak.set_max(n_live)
        util = self.kv_utilization()
        self._tm_kv.set(util)
        self._tm_kv_peak.set_max(util)
        self._tm_slots.set(self.allocator.slots_in_use)
        self._tm_slots_peak.set_max(self.allocator.slots_in_use)
        # a sequence that is done holds no block
        quarter, half = self._mb_tier_bounds()     # as _mb_tier_name's
        in_quarter = int(held[held <= quarter].sum())
        in_full = int(held[held > half].sum())
        self._tm_kv_tier.set(in_quarter, tier="quarter")
        self._tm_kv_tier.set(int(held.sum()) - in_quarter - in_full,
                             tier="half")
        self._tm_kv_tier.set(in_full, tier="full")

    def _observe_tok_lat(self, per_token_s: float, n: int,
                         now: float) -> None:
        """One funnel for every decode-latency observation: the global
        histogram AND the per-engine accumulators est_token_seconds
        reads (keeping multi-engine processes unblended). ``now``: the
        ``time.perf_counter()`` reading that ended the measured section."""
        self._tm_tok_lat.observe(per_token_s, n=n)
        self._tok_lat_sum += per_token_s * n
        self._tok_lat_n += n
        idx = int(now // self._tok_lat_win_interval_s)
        ring = self._tok_lat_win
        if not ring or ring[-1][0] != idx:
            ring.append([idx, 0.0, 0])
        while ring and ring[0][0] <= idx - self._tok_lat_win_intervals:
            ring.popleft()
        ring[-1][1] += per_token_s * n
        ring[-1][2] += n

    def _tm_first_token(self, seq: _Seq) -> None:
        if not seq.first_tok_seen:
            seq.first_tok_seen = True
            self._tm_ttft.observe(time.perf_counter() - seq.admit_t)

    def _mb_tier_bounds(self):
        """(quarter, half) table-width tier bounds — the single source both
        _mb_tier (compile-cache keys) and _mb_tier_name (metric labels)
        read from."""
        quarter = max(2, self.max_blocks_per_seq // 4)
        return quarter, max(quarter, self.max_blocks_per_seq // 2)

    def _mb_tier(self, mb_need: int) -> int:
        """Table-width tiers (quarter/half/full) — ONE rule for every
        compile-cache key, so that the cache stays a small grid. The tier
        is the width of the block tables a tick carries: the reference path
        gathers every covered block, the Pallas kernel holds the table in
        scalar memory and fetches only the blocks a row's length reaches
        (measured in PR 22, and by construction since PR 24: its walks
        stop at ``ceil(length / block_size)``), so for the kernel a
        narrower tier saves table bytes, not KV reads."""
        quarter, half = self._mb_tier_bounds()
        if mb_need <= quarter:
            return quarter
        if mb_need <= half:
            return half
        return self.max_blocks_per_seq

    def _bucket(self, need: int) -> int:
        """Two tick-size tiers (small for decode-heavy ticks, full budget
        otherwise) — each tier is one compiled program; admission
        composition never adds one."""
        small = max(8, self.token_budget // 8)
        return small if need <= small else self.token_budget

    # ------------------------------------------------------------------ #
    def _pack_tick(self, tokens: np.ndarray, positions: np.ndarray,
                   tables: np.ndarray, key: np.ndarray,
                   head_rows: Sequence[int] = ()) -> np.ndarray:
        """All a tick sends to the device, as ONE fresh contiguous int32
        host array: ``[tables row by row | tokens | positions | the
        sampled rows | the two key words]``. The sampled rows are there
        for a tick larger than the small bucket ``S`` alone: the first
        ``S`` of ``head_rows`` (the rows whose token the host will read),
        the last repeated to fill, and behind them their count. Fresh a
        tick: a numpy buffer handed to the runtime may not change until
        its transfer is done."""
        S, count = self._bucket(0), len(head_rows)
        head = np.empty((0,), np.int32)
        if len(tokens) > S:
            head = np.full((S + 1,), head_rows[-1] if count else 0, np.int32)
            head[:min(count, S)] = head_rows[:S]
            head[S] = count
        return np.concatenate(
            (tables.ravel(), tokens, positions, head, key.view(np.int32)))

    def _build_tick(self, Tn: int, mb: int):
        """The tick program of ``Tn`` rows over tables ``mb`` blocks wide.
        It takes ``_pack_tick``'s array and cuts it apart with static
        slices, so a tick crosses to the device once.

        A tick larger than the small bucket ``S`` samples few of its rows
        (the decode rows, a finished prompt's last): where they fit in
        ``S`` the head runs for those rows, gathered from the last hidden
        state, and their tokens come back in the order sent, at the front
        of the ``[Tn]`` result; where they do not, for every row as in the
        small bucket, a row's token at its row. One program either way:
        the count in the packed array chooses."""
        cfg, attn = self.cfg, self._attention
        n = Tn * mb
        S = self._bucket(0)
        looped = cfg.loop_passes > 1

        def tick(params, pool, packed):
            tables = packed[:n].reshape(Tn, mb)
            tokens = packed[n:n + Tn]
            positions = packed[n + Tn:n + 2 * Tn]
            # the same bits the host drew: a raw uint32[2] threefry key
            rng = jax.lax.bitcast_convert_type(packed[-2:], jnp.uint32)
            x, pool, stats = PG.forward_hidden(
                params, tokens, positions, tables, pool, cfg,
                attention_fn=attn)

            def sample(x):
                # with a temperature a row's draw comes from the key and
                # the row's place in ``x``: the gathered rows draw from
                # the same distribution by another stream
                logits = PG.head_logits(params, x, cfg, with_exit=looped)
                if looped:
                    logits, pdf = logits
                with jax.named_scope("sample"):
                    toks = sample_logits(
                        logits, rng, self.temperature, self.top_k,
                        self.top_p).astype(jnp.int32)
                if not looped:
                    return toks
                # one array, one read-back: a row's token and, behind it,
                # the bits of its exit distribution ([rows, 1 + passes])
                return jnp.concatenate(
                    [toks[:, None],
                     jax.lax.bitcast_convert_type(pdf, jnp.int32)], axis=1)

            if Tn > S:
                head_rows = packed[n + 2 * Tn:n + 2 * Tn + S]
                sampled = jax.lax.cond(
                    packed[n + 2 * Tn + S] <= S,
                    lambda x: jnp.pad(
                        sample(x[head_rows]),
                        ((0, Tn - S),) + ((0, 0),) * looped),
                    sample, x)
            else:
                sampled = sample(x)
            if self._expert_layers:
                # one array, one read-back: the rows each expert got
                # ([layers, E]) behind the sampled tokens
                sampled = jnp.concatenate(
                    [sampled, stats["expert_rows"].reshape(-1)])
            return sampled, pool

        return jax.jit(tick, donate_argnums=(1,))

    def collective_ledger(self, n_tokens: Optional[int] = None,
                          fold: bool = True):
        """Compiled-collective ledger of one mixed tick at the given
        token-budget bucket (execution-observatory hook): under TP this
        enumerates the row/col-parallel collectives GSPMD inserted into
        the tick program; single-replica serving legitimately ledgers
        empty. ``fold=True`` publishes ``comm_ledger_*`` metrics under
        ``program="fastgen_tick"``. Cached per engine."""
        from deepspeed_tpu.profiling.observatory import ledger_for_fastgen

        return ledger_for_fastgen(self, n_tokens=n_tokens, fold=fold)[0]

    # ------------------------------------------------------------------ #
    def can_schedule(self) -> bool:
        return self.allocator.free_blocks > 0

    def put(self, uids: Sequence[int], prompts: Sequence[Sequence[int]],
            deadline_s: Optional[float] = None) -> None:
        """Admit sequences — host bookkeeping ONLY (no device dispatch, no
        compile). Prefill happens chunked inside subsequent ``step()`` ticks
        (reference ``put`` :107 + SplitFuse chunking). ``deadline_s``
        overrides the engine's ``request_deadline_s`` for this admission
        batch: past the deadline the request is dropped at the next
        scheduling tick (``fastgen_deadline_expired_total``)."""
        if deadline_s is None:
            deadline_s = self.request_deadline_s
        # validate the WHOLE batch before mutating anything: a ValueError
        # mid-batch must not leave earlier uids of the same call admitted
        # (the caller sees an exception and retries the batch — partial
        # admission then double-admits the survivors)
        batch = []
        seen = set()
        for uid, prompt in zip(uids, prompts):
            prompt = list(prompt)
            if uid in self.seqs or uid in seen:
                raise ValueError(
                    f"uid {uid} is still active — flush() it before re-use")
            if len(prompt) >= self.max_len:
                raise ValueError(
                    f"prompt len {len(prompt)} >= max_len {self.max_len}")
            seen.add(uid)
            batch.append((uid, prompt))
        for uid, prompt in batch:
            self.seqs[uid] = _Seq(uid, prompt, self._rows,
                                  deadline_s=deadline_s)
            self._admit_order.append(uid)
        self._order = None
        self._tm_sched_gauges()

    def _order_rows(self) -> np.ndarray:
        """The rows of ``_admit_order``'s sequences, in its order."""
        if self._order is None:
            seqs = self.seqs
            self._order = np.array([seqs[u].row for u in self._admit_order],
                                   np.intp)
        return self._order

    def _expire_deadlines(self) -> int:
        """Drop live sequences past their deadline (blocks freed, marked
        done+expired) — the scheduler-side half of request cancellation.
        Runs at every dynamic scheduling entry point; a dropped request
        answers ``query()`` with done=True and whatever it generated."""
        st = self._rows
        late = st.live[:st.hi] & (st.deadline[:st.hi] < time.perf_counter())
        if not late.any():
            return 0
        order = self._order_rows()
        late = order[late[order]].tolist()        # in admission order
        for r in late:
            seq = st.seqs[r]
            state = "waiting" if seq.prefill_remaining > 0 else "running"
            seq.expired = True
            self._finish(seq)
            self._tm_deadline.inc(state=state)
        self._tm_sched_gauges()
        return len(late)

    def expired(self, uid: int) -> bool:
        """Whether ``uid`` was dropped by deadline expiry. Unknown or
        already-flushed uids return False — a status poll racing a flush
        must get an answer, not a KeyError (a flushed request is by
        definition no longer expiring)."""
        seq = self.seqs.get(uid)
        return seq.expired if seq is not None else False

    def kv_utilization(self, extra_blocks: int = 0) -> float:
        """Fraction of the USABLE KV pool allocated (block 0 is the
        reserved trash block and never counts as capacity) — the single
        source for both the telemetry gauge and the serving front-end's
        watermark checks. ``extra_blocks`` projects an admission's needs
        on top of current allocation."""
        cap = max(1, self.allocator.n_blocks - 1)
        return (cap - self.allocator.free_blocks + extra_blocks) / cap

    def est_token_seconds(self) -> Optional[float]:
        """Mean per-token decode latency observed by THIS engine (None
        before the first warm tick/window lands) — what the serving
        front-end turns into retry-after hints and deadline-slack
        estimates. Deliberately per-engine, not the process-global
        histogram: two engines in one process must not blend rates.
        Prefers the sliding-window mean (last ~60s) so one slow warmup
        tick can't skew routing scores forever; the lifetime mean is the
        fallback once the window has gone quiet."""
        if self._tok_lat_n == 0:
            return None
        now_idx = int(time.perf_counter() // self._tok_lat_win_interval_s)
        win_sum = win_n = 0
        for idx, s, n in self._tok_lat_win:
            if idx > now_idx - self._tok_lat_win_intervals:
                win_sum += s
                win_n += n
        if win_n:
            return win_sum / win_n
        return self._tok_lat_sum / self._tok_lat_n

    def _snapshot_host(self) -> tuple:
        """What step() needs to undo a failed tick: copies of the rows'
        arrays a tick may change (``_Rows.TICK_FIELDS``: a new field the
        scheduler mutates goes there), the rotation's offset, and from
        here on the allocator's journal of the blocks it moves. A few
        array copies whatever the rows, where a tuple, a list and a table
        a sequence grew with them. Already-emitted metric OBSERVATIONS
        (TTFT, token counters) cannot be unobserved: a tick that fails
        after sampling may leave a phantom sample; state consistency is
        the contract here, not metric exactness."""
        self.allocator.begin()
        return self._rows.snapshot(), self._decode_rr

    def _restore_host(self, snap: tuple) -> None:
        # generated is append-only within a tick, so the rows' ``gen_len``
        # restores it: what a tick kept is cut off again
        self._rows.restore(snap[0])
        self._decode_rr = snap[1]
        self.allocator.rollback()

    def _ensure_blocks(self, seq: _Seq, upto_pos: int) -> bool:
        """Grow the sequence's block table to cover ``upto_pos``. Returns
        False (leaving per-seq state untouched) when the pool can't supply
        the blocks — the scheduler then defers that sequence (capacity
        backpressure, reference ``scheduling_utils`` CacheBlock result)."""
        st, r = seq._st, seq.row
        held = st.held.item(r)
        grow = upto_pos // self.block_size + 1 - held
        if grow <= 0:
            return True
        if grow > self.allocator.available(starting=not held):
            return False
        # a sequence's first block comes with its slot (``BlockAllocator``)
        st.table[r, held:held + grow] = self.allocator.grow(grow) if held \
            else self.allocator.allocate(grow)
        st.held[r] = held + grow
        return True

    def step(self) -> Dict[int, int]:
        """One SplitFuse tick: decode every running sequence + prefill chunks
        under the token budget. Returns {uid: sampled token} for sequences
        that produced one this tick.

        Exception-safe: the scheduler advances host bookkeeping (prefilled,
        pos, block tables, allocator) BEFORE the device call lands, so any
        failure mid-tick (device fault, injected chaos, interrupt) rolls
        all of it back before re-raising — a caught tick failure leaves the
        engine consistent and retryable (what the serving front-end's
        circuit breaker relies on). A fault inside the dispatched program
        itself may still invalidate the donated KV pool; that is a
        dead-device condition the breaker answers with backoff, not state
        this rollback can save.

        The state a tick changes lives in the engine's ``_Rows`` and the
        allocator's two lists; the snapshot is the engine's
        (``_snapshot_host``), taken after the deadlines' sweep (an expiry
        is not undone) and before ``schedule_tick`` opens."""
        self._expire_deadlines()
        snap = self._snapshot_host()
        try:
            out = self._step_impl()
        except BaseException:
            self._restore_host(snap)
            raise
        self.allocator.commit()
        return out

    def _step_impl(self) -> Dict[int, int]:
        st, bs = self._rows, self.block_size
        # the host-side SplitFuse packing gets its own span so a tick's
        # timeline splits into schedule (host) vs dispatch (device) —
        # the first question about a slow tick is which side it was
        with telemetry.span("schedule_tick") as sched_span:
            # every test of the schedule is a pass over the rows in
            # admission order; a sequence meets Python below only where
            # something happens to it (``touched``: what
            # fastgen_tick_rows_total counts as the python path)
            order = self._order_rows()
            live = st.live[order]
            left = np.where(live, st.prompt_len[order] - st.prefilled[order],
                            0)              # prompt tokens still to write
            ready = live & (left == 0) & (st.last_tok[order] >= 0)
            Tn = self._bucket(int(np.count_nonzero(ready)) + int(left.sum()))
            tokens = np.zeros((Tn,), np.int32)
            positions = np.zeros((Tn,), np.int32)
            tables = np.zeros((Tn, self.max_blocks_per_seq), np.int32)
            touched = np.zeros((st.hi,), bool)
            # runs of rows that start from a sequence's stored state; and,
            # of a model with window layers, the cache positions inside
            # the rows' windows (what a window layer must read, a
            # sequence) and those the prompt rows score (a row)
            W = self.cfg.attn_window
            window_attended = 0
            # prompt rows in kernel tiles wholly inside one chunk
            shared_rows = 0
            R = self._tile_rows
            chunk_starts: List[int] = []       # each chunk's first row

            # 1) decode tokens — one per fully-prefilled live sequence,
            # starting from a rotating offset so tails never starve when
            # live sequences exceed the budget (the reference scheduler's
            # fairness rotation)
            at = np.flatnonzero(ready)
            cut = np.searchsorted(at, self._decode_rr % max(len(order), 1))
            dec = order[np.concatenate((at[cut:], at[:cut]))]
            # the rows whose next position lies past their blocks (one
            # tick in block_size): those the pool cannot serve wait a tick
            short = np.flatnonzero(st.pos[dec] // bs >= st.held[dec])
            waited = 0
            if short.size:
                stays = np.ones(dec.shape, bool)
                for i in short.tolist():
                    if i - waited >= Tn:
                        break           # past the budget: not this tick's
                    r = dec[i]
                    touched[r] = True
                    if not self._ensure_blocks(st.seqs[r], st.pos.item(r)):
                        self._tm_preempt.inc(phase="decode")
                        stays[i] = False
                        waited += 1
                dec = dec[stays]
            dec = dec[:Tn]
            row = n_decode_rows = len(dec)
            tokens[:row] = st.last_tok[dec]
            positions[:row] = st.pos[dec]
            tables[:row] = st.table[dec]
            state_runs = row
            window_positions = int(np.minimum(positions[:row] + 1, W).sum())
            self._decode_rr += 1

            # 2) prefill chunks — FIFO admission, split to fit the
            # remaining budget (Dynamic SplitFuse: long prompts stream
            # across ticks). A loop a chunk: there are few
            chunks = 0
            # a prompt's last row and its sequence's row: the first
            # generated token is sampled there
            end_rows: List[int] = []
            end_seqs: List[int] = []
            for r in order[left > 0].tolist():
                if row >= Tn:
                    break
                seq, pos, held = st.seqs[r], st.pos.item(r), st.held.item(r)
                touched[r] = True
                lo = st.prefilled.item(r)
                chunk = min(len(seq.prompt) - lo, Tn - row)
                # capacity backpressure: shrink the chunk to the blocks
                # the pool can actually supply; zero → the prompt waits
                # for a flush
                starting = not held
                fits = (held + self.allocator.available(starting)) * bs - pos
                chunk = min(chunk, fits)
                if chunk <= 0:
                    self._tm_preempt.inc(phase="prefill")
                    waited += 1
                    if starting and self.allocator.state_slots \
                            and not self.allocator.free_slots \
                            and not seq.slot_waited:
                        # waits as it would for blocks; counted once
                        seq.slot_waited = True
                        self._tm_slot_waits.inc()
                    continue
                self._ensure_blocks(seq, pos + chunk - 1)
                chunks += 1
                state_runs += pos > 0
                if W:
                    window_positions += min(pos + chunk, chunk + W - 1)
                    window_attended += int(np.minimum(
                        np.arange(pos, pos + chunk) + 1, W).sum())
                tokens[row:row + chunk] = seq.prompt[lo:lo + chunk]
                positions[row:row + chunk] = np.arange(pos, pos + chunk)
                tables[row:row + chunk] = st.table[r]
                chunk_starts.append(row)
                if R:
                    shared_rows += R * max(
                        0, (row + chunk) // R - -(-row // R))
                row += chunk
                st.prefilled[r] = lo + chunk
                st.pos[r] = pos + chunk
                if lo + chunk == len(seq.prompt):
                    end_rows.append(row - 1)
                    end_seqs.append(r)

        if row == 0:
            return {}

        # bucket the table width too (quarter/half/full tiers — each
        # (Tn, mb) pair is a compiled program): the tier bounds the KV
        # blocks the kernel walks AND DMAs, see _mb_tier
        mb_need = int(positions[:row].max()) // self.block_size + 1
        mb = self._mb_tier(mb_need)

        key = (Tn, mb)
        cold = key not in self._ticks
        if cold:
            self._ticks[key] = self._build_tick(Tn, mb)
        # the rows whose logits get sampled this tick, and the sequences'
        # rows they belong to: every decode row, then the prompts' ends
        n_heads = n_decode_rows + len(end_rows)
        head_rows = np.arange(n_heads)
        head_rows[n_decode_rows:] = end_rows
        heads = np.concatenate((dec, np.array(end_seqs, np.intp)))
        # the tick program's own rule (``_build_tick``)
        S = self._bucket(0)
        gathered = Tn > S and n_heads <= S
        head_computed = S if gathered else Tn
        # a tick that holds no prompt row is a decode tick, whatever
        # entry point ran it
        kind = "decode" if n_decode_rows == row else "mixed"
        tier = self._mb_tier_name(mb)
        self._ticks_run += 1
        slot_attrs = {}
        if self.allocator.state_slots:
            slot_attrs = {"state_runs": int(state_runs),
                          "state_slots": self.allocator.slots_in_use,
                          "window_positions": int(window_positions),
                          "window_attended": window_attended}
            for span in self._kind_spans:
                slot_attrs.update(span(n_decode_rows, chunk_starts, row, Tn,
                                       positions[:row] + 1))
        with telemetry.span("decode_tick", attrs={
                **slot_attrs, **self._loop_attrs,
                "tick": self._ticks_run, "kind": kind, "rows": row,
                "decode_rows": n_decode_rows,
                "prefill_tokens": row - n_decode_rows,
                # cache positions the prompt rows attend to (position + 1
                # a row; decode rows come first in the tick): with the
                # tick's device time, what its attention had to compute
                "prompt_attended": int(positions[n_decode_rows:row].sum())
                + row - n_decode_rows,
                "shared_rows": shared_rows, "bucket": Tn,
                # rows whose token is read back, rows the head ran for
                "head_rows": n_heads, "head_computed": head_computed,
                "mb_tier": tier}) as tick_span:
            packed = self._pack_tick(
                tokens, positions, tables[:, :mb],
                self._host_rng.integers(0, 2 ** 32, 2, dtype=np.uint32),
                head_rows)
            # enqueue: the jitted call on the one host array (its copy
            # to the device is the call's own), until it returns (the
            # device may still be running), and the copy back queued
            # behind the program
            with telemetry.span("tick_dispatch") as dispatch_span:
                sampled, self.pool = self._ticks[key](
                    self.params, self.pool,
                    packed if self._rep_sh is None
                    else jax.device_put(packed, self._rep_sh))
                sampled.copy_to_host_async()
            # while the device runs: the fetch steps its attention calls
            # walk, those that take the unmasked form and the walks (one a
            # run of rows under one table), by the kernel's own rule of the
            # tick's lengths
            attn_steps = attn_open = 0
            if self._walks:
                from deepspeed_tpu.ops.pallas.paged_attention import (
                    count_steps, count_walks)

                # the rows of whole tiles (the kernel's wrapper pads as the
                # tick does: length 1, the zero table) in runs that carry
                # one table: every decode row, every chunk, the pads
                lengths = np.ones((-(-Tn // R) * R,), np.int32)
                lengths[:Tn] = positions + 1
                starts = np.zeros(lengths.shape, bool)
                starts[:n_decode_rows] = True
                starts[chunk_starts + [row] * (row < len(starts))] = True
                for layers, window, step in self._walks:
                    n, n_open = count_steps(lengths, starts, R, step, window)
                    attn_steps += layers * n
                    attn_open += layers * n_open
                # which geometry ran: the positions a step of the tick's
                # widest walk carries
                tick_span.note(attn_steps=attn_steps,
                               attn_open_steps=attn_open,
                               attn_walks=count_walks(starts, R) * sum(
                                   layers for layers, _, _ in self._walks),
                               attn_step_positions=max(
                                   step for _, _, step in self._walks))
            # the queue's and the pool's gauges too: they read what the
            # schedule left and nothing the tokens will change
            self._tm_occup.set(row / Tn, phase=kind)
            self._tm_sched_gauges()
            # the wait for the device and for the copy queued behind it
            with telemetry.span("tick_readback") as readback_span:
                sampled = np.asarray(sampled)
            exit_mass = None
            if self._loop_attrs:
                # [Tn, 1 + passes]: the exit distribution of the rows
                # whose token is read, summed by pass
                pdf = sampled[:, 1:].view(np.float32)
                sampled = sampled[:, 0]
                exit_mass = (pdf[:n_heads] if gathered
                             else pdf[head_rows]).sum(axis=0)
            expert_rows, commit_attrs = None, None
            if self._expert_layers:
                all_rows = sampled[Tn:].reshape(self._expert_layers, -1)
                # the experts held here (all of them, but for a share of
                # the layer): the rows that were computed
                lo = self.cfg.moe_first_expert
                expert_rows = all_rows[:, lo:lo + self.cfg.n_experts]
                pairs, pairs_held = int(all_rows.sum()), \
                    int(expert_rows.sum())
                tick_span.note(
                    experts_max_rows=int(expert_rows.max()),
                    experts_mean_rows=float(expert_rows.mean()))
                # the tick's own annotation was written at entry: what
                # came back with the tokens rides on the span that follows.
                # Held experts with a row, summed over the expert layers:
                # times an expert's bytes, the weights this tick had to
                # read; the pairs on held experts: the rows it multiplied
                commit_attrs = {
                    "tick": self._ticks_run,
                    "experts_active": int((expert_rows > 0).sum()),
                    "expert_pairs": pairs, "expert_pairs_held": pairs_held}
        with telemetry.span("tick_commit", attrs=commit_attrs) as commit_span:
            if expert_rows is not None:
                self._tm_expert_pairs.inc(pairs_held, held="yes")
                self._tm_expert_pairs.inc(pairs - pairs_held, held="no")
                self._tm_held_rows.observe(
                    pairs_held / expert_rows.size, bucket=str(Tn))
                self._tm_expert_imbalance.observe(
                    float(expert_rows.max(axis=1).sum())
                    / max(float(expert_rows.mean(axis=1).sum()), 1e-9),
                    bucket=str(Tn))
            if not cold and n_decode_rows:
                # per-token rate from the dynamic tick too (servers
                # driving step() alone must still feed est_token_seconds
                # for retry-after/deadline-slack estimates). Tick wall
                # time over decode rows slightly OVERcounts when prefill
                # shares the tick — conservative in the right direction
                # for those hints. Cold keys fold the XLA compile into
                # wall time and are skipped.
                self._observe_tok_lat(
                    (commit_span.t0 - tick_span.t0) / n_decode_rows,
                    n_decode_rows, commit_span.t0)
            self._tm_ticks.inc(kind=kind, mb_tier=tier)
            self._tm_h2d.inc(packed.nbytes)
            self._tm_prefill_tok.inc(row - n_decode_rows)
            self._tm_head_rows.inc(
                head_computed, form="gathered" if gathered else "all")
            self._tm_shared_rows.inc(shared_rows)
            if "kda_step_rows" in slot_attrs:
                self._tm_kda_rows.inc(slot_attrs["kda_step_rows"],
                                      form="step")
                self._tm_kda_rows.inc(slot_attrs["kda_chunk_rows"],
                                      form="chunk")
                self._tm_kda_pieces.inc(slot_attrs["kda_chunk_pieces"])
            if "ssd_step_rows" in slot_attrs:
                self._tm_ssd_rows.inc(slot_attrs["ssd_step_rows"],
                                      form="step")
                self._tm_ssd_rows.inc(slot_attrs["ssd_chunk_rows"],
                                      form="chunk")
            if "sparse_selected" in slot_attrs:
                self._tm_index_positions.inc(slot_attrs["index_positions"])
                self._tm_sparse_selected.inc(slot_attrs["sparse_selected"])
                self._tm_sparse_read.inc(slot_attrs["sparse_positions_read"])
            if attn_steps:
                self._tm_attn_steps.inc(attn_open, form="open")
                self._tm_attn_steps.inc(attn_steps - attn_open,
                                        form="masked")
            if exit_mass is not None:
                self._tm_layer_apps.inc(self._loop_attrs["cache_layers"])
                for t, mass in enumerate(exit_mass.tolist()):
                    self._tm_exit_mass.inc(mass, **{"pass": str(t)})

            # every head's token folded into its sequence at once: a
            # sequence meets Python where it sees its first token or ends
            toks = sampled[:n_heads] if gathered else sampled[head_rows]
            st.pos[dec] += 1        # the decode input token entered the cache
            st.last_tok[heads] = toks
            # TTFT anchors on the FIRST sampled token even when it's EOS
            first = heads[~st.first_seen[heads]]
            if first.size:
                now = time.perf_counter()
                for r in first.tolist():
                    self._tm_ttft.observe(now - st.seqs[r].admit_t)
                st.first_seen[first] = touched[first] = True
            over = st.pos[heads] + 1 >= self.max_len
            answers = toks.tolist()
            kept_rows, kept_toks = heads, answers
            if self.eos_token_id is not None:
                kept = toks != self.eos_token_id
                over |= ~kept
                kept_rows, kept_toks = heads[kept], toks[kept].tolist()
            seqs = st.seqs
            for r, tok in zip(kept_rows.tolist(), kept_toks):
                seqs[r].generated.append(tok)
            st.gen_len[kept_rows] += 1
            for r in heads[over].tolist():      # in the heads' order: the
                touched[r] = True               # free list's order
                self._finish(seqs[r])
            out = dict(zip(st.uid[heads].tolist(), answers))
            self._tm_gen_tok.inc(len(kept_rows))
            python = int(np.count_nonzero(touched))
            self._tm_tick_rows.inc_keys(
                self._tick_rows_keys,
                (n_decode_rows + chunks + waited - python, python))
            # collections queued since the last tick: in the registry by
            # the time the tick ends; the process's own counters every
            # sixteenth tick (and at a slow one)
            telemetry.refresh_host_counters(
                process=not self._ticks_run % _PROCESS_REFRESH_TICKS)
        # the spans' own readings, each the end of one phase and the
        # start of the next
        self._account_tick(
            kind, Tn, mb, tier, row, cold,
            (sched_span.t0, tick_span.t0, dispatch_span.t0, dispatch_span.t1,
             readback_span.t0, readback_span.t1, commit_span.t1))
        return out

    def _account_tick(self, kind: str, Tn: int, mb: int, tier: str,
                      rows: int, cold: bool, at: tuple) -> None:
        """Where this tick's period went, and whether it was slow.

        ``at``: the clock readings that bound the tick's phases, first the
        entry of ``schedule_tick``, last the exit of ``tick_commit``. The
        period runs from the end of the previous tick to the end of this
        one; what of it lies before ``at[0]`` is ``outside`` the engine
        (the caller, the frontend around ``step()``). After a stretch
        without a live sequence the period starts at ``at[0]`` and the
        stretch counts as idle, so over any run of ticks wall time =
        periods + idle, and a period = outside + the six phases.

        A program is (kind, row bucket, table width). Its typical parts
        are a mean fed by the ticks inside the limit only, so a slow tick
        moves nothing; a slow tick is counted under the part whose own
        excess over its typical value is largest, and remembered in
        ``slow_ticks``. Cold ticks (the program compiled inside them) and
        a program's first ticks are not judged.

        6.2 us a tick measured on the sandbox's CPU, 8.5 with the rest of
        what a tick pays for its account
        (``tests/unit/test_tick_account.py``'s guard)."""
        start, end = at[0], at[-1]
        if self._tick_end_t is not None:
            if self._idle:
                self._tm_idle.inc(start - self._tick_end_t)
            else:
                start = self._tick_end_t
        self._tick_end_t, self._idle = end, False
        gc_seen = telemetry.gc_pause_seconds()
        gc_s, self._gc_seen_s = gc_seen - self._gc_seen_s, gc_seen
        # seconds under JAX's compile path since the last tick's end (a
        # tick that traced, lowered or compiled anything says so itself)
        compile_seen = telemetry.compile_seconds()
        compile_s = compile_seen - self._compile_seen_s
        self._compile_seen_s = compile_seen
        parts = (at[0] - start, at[1] - at[0], at[2] - at[1], at[3] - at[2],
                 at[4] - at[3], at[5] - at[4], at[6] - at[5])
        period = end - start
        keys = self._period_keys.get((kind, Tn))
        if keys is None:
            keys = self._period_keys[(kind, Tn)] = (
                telemetry.label_key(kind=kind, bucket=f"T{Tn}"),
                tuple(telemetry.label_key(phase=p, kind=kind)
                      for p in TICK_PHASES))
        self._tm_period.observe_key(keys[0], period)
        self._tm_phase.inc_keys(keys[1], parts[1:])
        if cold:
            return
        typ = self._typical.get((kind, Tn, mb))
        if typ is None:
            self._typical[(kind, Tn, mb)] = [1, 0, *parts]
            return
        typical = sum(typ[2:])
        if typ[0] >= _TYPICAL_WARMUP and period - typical >= max(
                SLOW_TICK_MIN_S, (SLOW_TICK_RATIO - 1.0) * typical):
            self._note_slow_tick(kind, Tn, mb, tier, rows, parts, typ, gc_s,
                                 compile_s, end)
            return
        if not self._ticks_run % _PROCESS_REFRESH_TICKS:
            self._cpu_seen = (end, time.process_time(), time.thread_time())
        typ[0] += 1
        typ[1] = 0
        share = 1.0 / min(typ[0], _TYPICAL_SPAN)
        for i, x in enumerate(parts, 2):
            typ[i] += share * (x - typ[i])

    def _note_slow_tick(self, kind: str, Tn: int, mb: int, tier: str,
                        rows: int, parts: tuple, typ: List[float],
                        gc_s: float, compile_s: float, end: float) -> None:
        typical = typ[2:]
        over = [x - t for x, t in zip(parts, typical)]
        owner = _PERIOD_PARTS[over.index(max(over))]
        period, usual = sum(parts), sum(typical)
        seen = self._cpu_seen
        now = self._cpu_seen = (end, time.process_time(), time.thread_time())
        self._tm_slow.inc(phase=owner, kind=kind)
        self._tm_slow_excess.inc(period - usual, phase=owner, kind=kind)
        # the tick's number is the one its decode_tick annotation carries:
        # a slow tick of a traced stretch is found in the device trace
        # through that span's run_id
        record = {"tick": self._ticks_run, "engine": self.engine_no,
                  "kind": kind, "bucket": Tn, "mb_tier": tier, "rows": rows,
                  "phase": owner, "period_s": period,
                  "typical_period_s": usual, "gc_s": gc_s,
                  "compile_s": compile_s,
                  # CPU seconds, every thread's and this one's, of the
                  # ``cpu_wall_s`` that end with this tick (at most sixteen
                  # ticks): short of that wall by about the stall, the
                  # process was not running (descheduled, or frozen)
                  "cpu_s": now[1] - seen[1], "thread_cpu_s": now[2] - seen[2],
                  "cpu_wall_s": end - seen[0],
                  **{f"{p}_s": x for p, x in zip(_PERIOD_PARTS, parts)},
                  **{f"typical_{p}_s": t
                     for p, t in zip(_PERIOD_PARTS, typical)}}
        self.slow_ticks.append(record)
        self._tracer.event("slow_tick", **record)
        telemetry.refresh_host_counters()
        typ[1] += 1
        if typ[1] >= _SLOW_STREAK:
            del self._typical[(kind, Tn, mb)]

    def _finish(self, seq: _Seq) -> None:
        """Mark done and release KV blocks immediately — a finished sequence
        never decodes again, and holding its blocks until flush() starves
        waiting prompts (livelock if the caller only flushes at the end)."""
        st, r = seq._st, seq.row
        seq.done = True
        st.live[r] = False
        held = st.held.item(r)
        if held:
            self._tm_evict.inc(held)
        self._tm_finished.inc()
        self.allocator.free(st.table[r, :held].tolist())
        st.held[r] = 0
        st.table[r, :held] = 0

    def query(self, uid: int):
        d = self.seqs[uid]
        return d.done, list(d.generated)

    def rematerialize(self, uid: int) -> Optional[Dict[str, Any]]:
        """Host-side request snapshot for resubmission on a DIFFERENT
        engine (fleet failover/migration): the original prompt, the tokens
        generated so far, and how much of the prompt was prefilled. All
        host bookkeeping — KV blocks are device-local and stay behind; a
        new engine re-prefills ``prompt + generated`` as its prompt, which
        under greedy decoding continues the stream bit-identically. None
        for unknown uids (already flushed — nothing left to carry)."""
        seq = self.seqs.get(uid)
        if seq is None:
            return None
        return {"prompt": list(seq.prompt),
                "generated": list(seq.generated),
                "prefilled": seq.prefilled}

    def flush(self, uids: Sequence[int]) -> None:
        for uid in uids:
            d = self.seqs.pop(uid, None)
            if d is not None:
                blocks = d.blocks
                if blocks:
                    self._tm_evict.inc(len(blocks))
                self.allocator.free(blocks)
                d.done = True
                self._rows.release(d)
                self._admit_order.remove(uid)
                self._order = None
        self._tm_sched_gauges()

    def generate_all(self, uids, prompts, max_new_tokens: int = 32):
        """The tests' driver: ``put``, ``step()`` until every uid has
        ``max_new_tokens`` tokens or is done (a sequence is finished at its
        count, so its blocks go back while the others run), ``query``,
        ``flush``. Returns ``{uid: tokens}``."""
        self.put(uids, prompts)
        while True:
            for u in uids:
                s = self.seqs[u]
                if not s.done and len(s.generated) >= max_new_tokens:
                    self._finish(s)
            if all(self.seqs[u].done for u in uids):
                break
            if not self.step() and not any(
                    s.prefill_remaining > 0 and not s.done
                    for s in self.seqs.values()):
                break  # stalled: no tokens and nothing left to prefill
        out = {u: self.query(u)[1][:max_new_tokens] for u in uids}
        self.flush(uids)
        return out
