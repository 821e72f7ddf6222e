"""What the grouped matmul needs in an expert layer that holds a SHARE of
its experts (``moe/layer.py::dropless_moe_ffn`` told which experts it
holds; the Mosaic call is ``gmm``, as in ``expert_gmm.py`` beside this
file, which has the operands).

``expert_gmm.py`` takes a tick's pairs as its real rows times the experts a
token: right where every expert is held. Here the router chooses among all
the model's experts and only the pairs that fall on a held one are rows of
the matmul (the others sort behind the groups and are never multiplied), so
the pairs are a run-time value too: the program counts them from the rows
per expert it reads back and writes the sum over the expert layers on the
span that follows the tick (``tick_commit``'s ``expert_pairs_held``, beside
``experts_active``: HELD experts with a row). A layer's share of either sum
is taken as the mean over the layers. A program without ``expert_pairs_held``
(every other model; the parent) gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import expert_gmm


def classify(op) -> Optional[str]:
    return expert_gmm.classify(op)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    ticks = [(t, its) for t, its in tick_attrs.calls_by_tick(
        tick_attrs.per_tick(run), calls) if "expert_pairs_held" in t]
    layers = sum(c.num_layers for _, c in run.model.segments if c.n_experts)
    if not calls or not ticks or not layers:
        return None
    total, by_compute = 0.0, 0.0
    for t, its in ticks:
        for call in its:
            ops, moved = expert_gmm.ops_and_bytes(
                call.text, t["experts_active"] / layers,
                int(round(t["expert_pairs_held"] / layers)))
            mem = moved / run.peaks["hbm_bytes_per_s"]
            mxu = ops / run.peaks["bf16_flops_per_s"]
            total += max(mem, mxu)
            by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
