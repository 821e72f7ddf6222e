#!/usr/bin/env python3
"""The readings the ``granitemoehybrid`` cell's ``logits_check.rel_tol`` is
set from, and what the check can and cannot see:
``tools/nemotron_h_probe.py``'s stream of ticks, readings and output (its
options and what each of ``--do system,lower,mistakes,faults`` reads are said
there), run on this family's cell.

    chiprun -- python benchmarks/tools/granite_hybrid_probe.py --workload <cell> \
        --seeds 1,2 --do system,lower,mistakes,faults \
        [--depth 4 --dtype float32 --blocks 2000 --slots 8 \
         --gmm-tile 128,512,384] [--out file]

What is this family's own: the ``mistakes`` are the ``FAULTS`` of
``reference/granite_hybrid_lm.py`` (the residual, embedding and logits
scalars left at 1, the scores' factor ``head_dim ** -0.5``, the shared MLP
dropped, the experts' gate dropped, the gated norm by 8 groups, rotary on
the attention layer, one expert a token fewer, the decay dropped), and
``--depth D`` cuts the stack to the D layers that END with the cut's
attention layer (``layer_types``; ``nemotron_h`` names its layers by a
pattern string, so its own ``--depth`` is not used). ``lower`` is the same
edit of ``reference._linear``. ``--gmm-tile tm,tk,tn``: a chunk tick's 284
rows a held expert go through ``moe.layer.gmm_tilings``' tiles, not the
serving form's weight tile that ``nemotron_h_probe`` narrows: cut for two
bytes, they ask 86.7 MB of scoped VMEM in float32 at precision "highest"
(tiles of 512 x 1,024 x 768); these three numbers replace them, and the
last two go on as the weight tile.

``--rehearse 1``: the cell's rehearsal size, a dry run on the CPU.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--out", default="granite_hybrid_probe.json")
    ap.add_argument("--gmm-tile", default="")
    mine, rest = ap.parse_known_args()

    from benchmarks import manifest
    from benchmarks.tools import nemotron_h_probe

    load_cell = manifest.load_cell

    def cut_cell(name):
        cell = load_cell(name)
        if mine.depth:
            kinds = cell.config["layer_types"]
            last = kinds.index("attention") + 1
            cell.config["as_run"]["serve"]["num_hidden_layers"] = mine.depth
            cell.config["layer_types"] = kinds[last - mine.depth:last]
        return cell

    manifest.load_cell = cut_cell
    if mine.gmm_tile:
        from deepspeed_tpu.moe import layer as moe_layer

        tiles = tuple(int(n) for n in mine.gmm_tile.split(","))
        moe_layer.gmm_tilings = lambda M, K, N, groups, itemsize: (tiles,) * 3
        rest += ["--gmm-tile", ",".join(map(str, tiles[1:]))]
    sys.argv[1:] = rest + ["--out", mine.out]
    return nemotron_h_probe.main()


if __name__ == "__main__":
    sys.exit(main())
