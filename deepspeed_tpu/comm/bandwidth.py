"""Collective bandwidth math — THE one copy of the busbw correction factors.

``utils/comms_logging.calc_bw_log`` and ``utils/comm_bench`` used to each
carry their own factor table; at world size *n* the two could (and briefly
did) disagree about which ops get the ``(n-1)/n`` ring correction, which
made "busbw" in a bench row and "busbw" in a CommsLogger summary silently
different quantities. Both now import from here, and the compiled-collective
ledger (``profiling/observatory``) uses the same table for its *predicted*
bandwidths — so a wire-byte diff across rounds compares one convention.

Conventions (NCCL-tests / reference ``comms_logging.py``):

* ``size_bytes`` is the FULL logical tensor (the gathered/reduced result,
  not the per-rank shard) — algbw = size / time;
* busbw = algbw × factor, where the ring factor is ``2(n-1)/n`` for
  all-reduce (reduce-scatter + all-gather wire phases) and ``(n-1)/n``
  for all-gather / reduce-scatter / all-to-all (each rank moves all but
  its own shard);
* point-to-point shuffles (collective-permute / broadcast / unknown ops)
  take factor 1.0 — algbw is already the wire rate.

Stdlib-only: importable before jax loads (bench orchestrator, HLO parser).
"""
from __future__ import annotations

from typing import Dict

from deepspeed_tpu.utils.chip_specs import lookup_chip

#: canonical collective kinds (the ledger's vocabulary)
ALL_REDUCE = "all_reduce"
ALL_GATHER = "all_gather"
REDUCE_SCATTER = "reduce_scatter"
ALL_TO_ALL = "all_to_all"
COLLECTIVE_PERMUTE = "collective_permute"
BROADCAST = "broadcast"
UNKNOWN = "unknown"

COLLECTIVE_KINDS = (ALL_REDUCE, ALL_GATHER, REDUCE_SCATTER, ALL_TO_ALL,
                    COLLECTIVE_PERMUTE, BROADCAST, UNKNOWN)

# every alias the reference API, jax lax names, and HLO opcodes use for
# the same logical collective
_ALIASES: Dict[str, str] = {
    # reference deepspeed comm op names
    "all_reduce": ALL_REDUCE, "inference_all_reduce": ALL_REDUCE,
    "all_reduce_coalesced": ALL_REDUCE,
    "all_gather": ALL_GATHER, "all_gather_into_tensor": ALL_GATHER,
    "all_gather_object": ALL_GATHER,
    "reduce_scatter": REDUCE_SCATTER, "reduce_scatter_tensor": REDUCE_SCATTER,
    "all_to_all": ALL_TO_ALL, "all_to_all_single": ALL_TO_ALL,
    "broadcast": BROADCAST, "broadcast_object_list": BROADCAST,
    # jax lax spellings
    "psum": ALL_REDUCE, "pmean": ALL_REDUCE,
    "psum_scatter": REDUCE_SCATTER,
    "ppermute": COLLECTIVE_PERMUTE, "pshuffle": COLLECTIVE_PERMUTE,
    # HLO opcodes (async -start variants normalize in canonical_kind)
    "all-reduce": ALL_REDUCE,
    "all-gather": ALL_GATHER,
    "reduce-scatter": REDUCE_SCATTER,
    "all-to-all": ALL_TO_ALL,
    "collective-permute": COLLECTIVE_PERMUTE,
    "collective-broadcast": BROADCAST,
}


def canonical_kind(op: str) -> str:
    """Map any op spelling (reference API name, jax lax name, HLO opcode,
    including async ``-start``/``-done`` variants) to a canonical kind;
    unrecognized spellings → ``"unknown"`` (never raises)."""
    name = (op or "").strip().lower()
    for suffix in ("-start", "-done"):
        if name.endswith(suffix):
            name = name[: -len(suffix)]
    return _ALIASES.get(name, UNKNOWN)


def busbw_factor(op: str, n: int) -> float:
    """Bus-bandwidth correction factor for ``op`` at group size ``n``.

    busbw = algbw × factor. ``n <= 1`` is a degenerate group (no wire
    traffic) — factor 0 for the ring collectives, 1 for point-to-point.
    """
    n = int(n)
    kind = canonical_kind(op)
    if n <= 1:
        return 0.0 if kind in (ALL_REDUCE, ALL_GATHER, REDUCE_SCATTER,
                               ALL_TO_ALL) else 1.0
    if kind == ALL_REDUCE:
        return 2.0 * (n - 1) / n
    if kind in (ALL_GATHER, REDUCE_SCATTER, ALL_TO_ALL):
        return (n - 1) / n
    # collective-permute / broadcast / unknown: the message rate IS the
    # wire rate
    return 1.0


def bw_log(op: str, size_bytes: int, duration_s: float,
           n: int) -> Dict[str, float]:
    """Algorithmic + bus bandwidth of one timed collective (GB/s) — the
    body behind ``utils/comms_logging.calc_bw_log``."""
    duration_s = max(float(duration_s), 1e-9)
    tput = float(size_bytes) / duration_s
    return {"tput_GBps": tput / 1e9,
            "busbw_GBps": tput * busbw_factor(op, n) / 1e9}


# --------------------------------------------------------------------- #
# datasheet link bandwidth (the ledger's comm-time prediction referent)
# --------------------------------------------------------------------- #

#: aggregate ICI bandwidth per chip, GB/s (datasheet: v4 2400 Gb/s,
#: v5e 1600, v5p 4800, v6e/Trillium 3584)
ICI_GBPS = {"v4": 300.0, "v5e": 200.0, "v5 lite": 200.0,
            "v5p": 600.0, "v6e": 448.0, "v6 lite": 448.0}

#: rate for a non-TPU device kind (CPU hosts, tests): software collectives
#: through shared memory land in this order
DEFAULT_LINK_GBPS = 10.0


def chip_link_gbps(device_kind: str, default: float = DEFAULT_LINK_GBPS) -> float:
    """Per-chip ICI GB/s for a PJRT ``device_kind`` string; a TPU kind
    missing from :data:`ICI_GBPS` raises (``chip_specs.lookup_chip``)."""
    return lookup_chip(ICI_GBPS, device_kind, default, "ICI GB/s")


def predicted_seconds(op: str, size_bytes: int, n: int,
                      link_gbps: float) -> float:
    """Predicted wire time of one collective at the given per-chip link
    bandwidth: bus bytes (size × busbw factor) over the link rate."""
    if link_gbps <= 0:
        return 0.0
    return float(size_bytes) * busbw_factor(op, n) / (link_gbps * 1e9)
