#!/usr/bin/env python3
"""What XLA re-lays in a serving cell's tick programs, read with no chip:
each ``(rows, table tier)`` program of a cell compiled at the cell's real
sizes for a described v5e, and its copies counted.

    python tools/tick_program_copies.py serve-kimi-linear-48b-rollout-closed
    python tools/tick_program_copies.py <cell> --programs 256x80 --min-mb 8

One JSON line a program: the ``remat_compressed`` / ``remat_uncompressed``
fusions by result shape (the rematerialization pass re-laying an array to
"save" the padding of its tiles), the ``copy`` instructions whose result is
above ``--min-mb`` by shape, the ``copy`` / ``reshape`` / ``transpose``
instructions as large as a per-slot state store of the pool
(``whole_store_copies``: a reshape the compiler could not make a
``bitcast`` moves every byte too), the fusions, copies and slices above
``--min-mb`` that do nothing but hand on entry parameters under ``params``
(``weight_copies``: weights written a second time every call; it guards
``T.scan_periods``, whose run of layers cut from stacked leaves ahead of
its loop was 0.97 GB of such copies a tick: PERF.md, PR 62),
``memory_analysis()``'s arguments / alias / temporaries, and two hashes:
of the program as lowered, and of the COMPILED text with source metadata
stripped and instructions renumbered in their order of appearance
(:func:`normalised`). Either equal on two trees:
the change between them left that program alone; the second alone equal:
it changed what was traced and nothing the device runs. Beside the tick
programs, the benchmark runner's ``check_logits`` program (``forward_paged``
over the whole token budget and the widest table, the pool donated) as
``"form": "check_logits"``. The pool rides a tick aliased in place: a copy
of a whole store, or a ``remat`` pair on one, is device time no layer needs
(PERF.md, PR 43: a store whose second-minor dimension was 3 taps was
re-laid ten times a decode tick).

The configuration, the pool and the packed tick are ``ShapeDtypeStruct``s:
nothing is allocated and nothing runs (~25 s a program of the largest cell
on this box). It reads the cell's and its configuration's files and edits
nothing. Say ``JAX_PLATFORMS=cpu``: the topology is described, not reached.
"""
import argparse
import collections
import hashlib
import json
import math
import os
import re
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: `` %name = type[dims]{layout} opcode(``: an instruction of compiled text
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\](\{[^ ]*\})? ([\w\-]+)\(")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}


#: the operands of an instruction whose line ``_INSTRUCTION`` matched
_OPERANDS = re.compile(r"%([\w.\-]+)")


def count_copies(text: str, stores=(), min_bytes: int = 8 << 20):
    """The re-layouts of a compiled program's text. ``stores``: element
    counts of the arrays no copy should be as large as (the pool's state
    stores). Returns ``{"remat": {shape: n}, "copies": {shape: n},
    "whole_store_copies": {shape: n}, "weight_copies": {shape: n}}``:
    instructions named ``*remat_compressed*`` / ``*remat_uncompressed*``,
    ``copy`` results of at least ``min_bytes``, the ``copy`` / ``reshape``
    / ``transpose`` results with a store's element count (what is left of
    a reshape in compiled text is no ``bitcast``: it moves the array), and
    the ``fusion`` / ``copy`` / ``slice`` / ``dynamic-slice`` results of at
    least ``min_bytes`` in the ENTRY computation whose every operand is an
    entry parameter under ``params`` or a ``bitcast`` of one (a product
    takes rows too: such an instruction writes weights again, ahead of the
    loop that reads them), but for results in another memory space
    (``S(1)``: a prefetch, no second copy in HBM)."""
    found = {k: collections.Counter() for k in (
        "remat", "copies", "whole_store_copies", "weight_copies")}
    stores, entry, weights = set(stores), False, {}
    for line in text.splitlines():
        entry = entry and line != "}" or line.startswith("ENTRY ")
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, dtype, dims, layout, opcode = m.groups()
        shape = f"{dtype}[{dims}]{layout or ''}"
        elements = math.prod(int(d) for d in dims.split(",") if d)
        big = elements * _ITEMSIZE.get(dtype, 4) >= min_bytes
        if entry:
            operands = _OPERANDS.findall(
                line[m.end():line.index(")", m.end())])
            handed = operands and all(o in weights for o in operands)
            if opcode == "parameter" and name.startswith("params__"):
                # ``params__blocks____mamba2____w_in__.1``: the leaf's path
                weights[name] = "/".join(
                    filter(None, name.rsplit(".", 1)[0].split("__")))
            elif opcode == "bitcast" and handed:
                weights[name] = weights[operands[0]]
            elif handed and big and "S(" not in shape and opcode in (
                    "fusion", "copy", "slice", "dynamic-slice"):
                found["weight_copies"][" ".join(
                    [*(weights[o] for o in operands), "->", shape])] += 1
        if "remat_compressed" in name or "remat_uncompressed" in name:
            found["remat"][shape] += 1
        if opcode in ("copy", "reshape", "transpose") and elements in stores:
            found["whole_store_copies"][f"{shape} {opcode}"] += 1
        if opcode == "copy" and big:
            found["copies"][shape] += 1
    return {k: dict(v) for k, v in found.items()}


#: what of a compiled program's text is its source's and not the program's
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.*\n)*?\n",
    re.M)
_NAME = re.compile(r"%[A-Za-z_][\w.\-]*")
#: a parameter's name in a computation's header: ``(param_0.38: s32[29], ``
_HEADER_NAME = re.compile(r"(?<=[(,] )[A-Za-z_][\w.\-]*(?=: )|(?<=\()"
                          r"[A-Za-z_][\w.\-]*(?=: )")
_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = [^=]*? custom-call\(", re.M)


def normalised(text: str) -> str:
    """Compiled text as two trees can be compared by: the ``metadata``
    of every instruction and the tables of file and function names taken
    out, and every name (``%fusion.207``, ``%region_3.41.clone.sunk``)
    replaced by the order of its first appearance, so that the same
    instructions with the same wiring in the same order are the same
    text, whichever numbers the tracer's counters gave them. A custom
    call keeps the stem of its name (a Mosaic call's name is what the
    benchmark's readers find it by); any other name's stem is that of
    whichever source operation XLA derived the instruction from last (a
    ``broadcast`` named ``add.1551`` on one tree and
    ``broadcast_in_dim.1551`` on the other), which says nothing of what
    it computes: its opcode, operands, shape and layout stay."""
    text = _HEADER_NAME.sub("", _TABLES.sub("", _METADATA.sub("", text)))
    calls = set(_CUSTOM_CALL.findall(text))
    seen = {}

    def renumber(m):
        name = m.group(0)
        stem = name.rsplit(".", 1)[0] if name in calls else "%"
        return seen.setdefault(name, f"{stem}#{len(seen)}")

    return _NAME.sub(renumber, text)


def describe_chip():
    """A v5e chip to compile for (raises where none can be described)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _bare_engine(cfg, sizes):
    """A ``FastGenEngine`` that was never built: ``_build_tick``, the
    bucket and the tier rules need its configuration, its sizes and its
    sampling constants alone."""
    from deepspeed_tpu.inference.fastgen import FastGenEngine

    eng = FastGenEngine.__new__(FastGenEngine)
    eng.cfg, eng.token_budget = cfg, sizes["token_budget"]
    eng.max_blocks_per_seq = sizes["max_blocks_per_seq"]
    eng.temperature, eng.top_k, eng.top_p = 0.0, 0, 1.0
    eng._expert_layers = sum(
        c.num_layers for _, c in cfg.segments if c.n_experts)
    return eng


def cell_programs(cell_name: str):
    """(model config, the cell's engine sizes, its ``(rows, tier)``
    programs) of a serving cell of the benchmark."""
    from benchmarks import model_config
    from benchmarks.manifest import load_cell

    cell = load_cell(cell_name)
    cfg, sizes = model_config.build(cell.config, "serve"), \
        cell.deploy["engine"]
    eng = _bare_engine(cfg, sizes)
    tiers = sorted({*eng._mb_tier_bounds(), eng.max_blocks_per_seq})
    rows = sorted({eng._bucket(0), eng.token_budget})
    return cfg, sizes, [(r, t) for r in rows for t in tiers]


def lower_tick(cfg, sizes, rows: int, tier: int, chip,
               form: str = "tick"):
    """One tick program lowered for ``chip``: (lowered, the pool's
    shapes). ``form`` ``check_logits``: the runner's check instead, the
    paged forward over ``rows`` rows and tables of ``tier`` blocks."""
    import numpy as np

    from deepspeed_tpu.models import paged as PG
    from deepspeed_tpu.models import transformer as T

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    dt = cfg.compute_dtype
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, dt if jnp.issubdtype(x.dtype, jnp.floating)
            else x.dtype),
        jax.eval_shape(lambda k: T.init_params(cfg, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    pool = jax.eval_shape(lambda: PG.init_paged_kv(
        cfg, sizes["n_blocks"], sizes["block_size"],
        state_slots=sizes.get("state_slots", 0) if cfg.layer_kinds else 0,
        max_run=sizes["token_budget"]))
    eng = _bare_engine(cfg, sizes)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        if form == "check_logits":
            from deepspeed_tpu.ops.pallas.paged_attention import \
                paged_attention

            ints = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=chip)
            return jax.jit(
                lambda params, pool, tokens, positions, tables:
                PG.forward_paged(params, tokens, positions, tables, pool, cfg,
                                 attention_fn=paged_attention),
                donate_argnums=(1,)).lower(
                    on_chip(params), on_chip(pool), ints, ints,
                    jax.ShapeDtypeStruct((rows, tier), jnp.int32,
                                         sharding=chip)), pool
        eng._attention, eng._tile_rows = PG.tick_attention(cfg, True)
        packed = eng._pack_tick(
            np.zeros((rows,), np.int32), np.zeros((rows,), np.int32),
            np.zeros((rows, tier), np.int32), np.zeros((2,), np.uint32))
        lowered = eng._build_tick(rows, tier).lower(
            on_chip(params), on_chip(pool), jax.ShapeDtypeStruct(
                packed.shape, jnp.int32, sharding=chip))
    return lowered, pool


def program_line(cfg, lowered, pool, min_bytes: int, text_path: str = ""):
    """What ``count_copies`` finds in a tick program once compiled, its
    memory, a hash of the program as LOWERED and one of the compiled text
    :func:`normalised` (``main`` keeps source lines out of both). The
    state stores are the pool's that are no BLOCKS of the model's table
    of cache kinds."""
    from deepspeed_tpu.models import paged as PG

    compiled = lowered.compile()
    text = compiled.as_text()
    if text_path:
        with open(text_path, "w") as f:
            f.write(text)
    stores = {s.name: math.prod(pool[s.name].shape)
              for _, s in PG.pool_stores(cfg) if s.cls != PG.BLOCKS}
    stats = compiled.memory_analysis()
    return {**count_copies(text, stores.values(), min_bytes),
            "state_stores": {k: list(pool[k].shape) for k in stores},
            "pool_bytes": sum(math.prod(v.shape) * v.dtype.itemsize
                              for v in pool.values()),
            "argument_gb": round(stats.argument_size_in_bytes / 1e9, 3),
            "alias_gb": round(stats.alias_size_in_bytes / 1e9, 3),
            "temp_gb": round(stats.temp_size_in_bytes / 1e9, 3),
            "lowered_sha256": hashlib.sha256(
                lowered.as_text().encode()).hexdigest()[:16],
            "compiled_sha256": hashlib.sha256(
                normalised(text).encode()).hexdigest()[:16]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell", help="a serving cell of BENCHMARK.json")
    ap.add_argument("--programs", default="",
                    help="ROWSxTIER,... (default: every program of the cell)")
    ap.add_argument("--min-mb", type=float, default=8.0,
                    help="list copies of at least this many MiB")
    ap.add_argument("--text-dir", default="",
                    help="keep each program's compiled text here")
    args = ap.parse_args(argv)
    # a Mosaic call serialises its body with the call stack of every
    # operation: without this an edit that shifts a line moves the hash
    jax.config.update("jax_traceback_in_locations_limit", 0)
    chip = describe_chip()
    cfg, sizes, programs = cell_programs(args.cell)
    if args.programs:
        programs = [tuple(int(n) for n in p.split("x"))
                    for p in args.programs.split(",")]
    if args.text_dir:
        os.makedirs(args.text_dir, exist_ok=True)
    # the runner's check beside them: the budget's rows, the widest table
    check = (sizes["token_budget"], sizes["max_blocks_per_seq"])
    for form, (rows, tier) in [("tick", p) for p in programs] + [
            ("check_logits", check)] * (not args.programs):
        lowered, pool = lower_tick(cfg, sizes, rows, tier, chip, form)
        print(json.dumps({
            "cell": args.cell, "form": form, "rows": rows, "tier": tier,
            **program_line(cfg, lowered, pool, int(args.min_mb * 2 ** 20),
                           args.text_dir and os.path.join(
                               args.text_dir,
                               f"{args.cell}-{form}-{rows}x{tier}.txt"))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
