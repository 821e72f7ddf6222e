#!/usr/bin/env python3
"""Record the small traces of the real gap chain kept under
``benchmarks/testdata/`` (``v5e_chain_<runner>.xplane.pb``).

Run on the chip (``chiprun -- python benchmarks/tools/capture_chain.py``):
for one serving and one training cell, the cell's own runner with a short
window and a traced stretch of a fraction of a second (a few ticks, a few
steps), the serving model cut to two layers so that a tick is a few
hundred device events and not thousands. What is kept of each trace is
chip 0's plane and the host's (``slim``); the chain's report for each goes
to standard output and to ``chiprun_out/chain/report.json``, so that the
test on the recording has numbers from the chip to hold it to. Not part of
any cell.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _field(number: int, payload) -> bytes:
    """A length-delimited field, re-encoded."""
    return _varint((number << 3) | 2) + _varint(len(payload)) + bytes(payload)


#: of an instruction's text the recording keeps this much: the name, the
#: result's type and the opcode (the operands' shapes are two thirds of a
#: trace of this size)
TEXT_KEPT = 200


def _cut(text: str) -> str:
    """The head of an instruction's text, with what the reducer reads of
    the rest put back: the opcode, and that it is a Mosaic call."""
    from benchmarks import trace_reduce as tr

    if len(text) <= TEXT_KEPT:
        return text
    head, kind = text[:TEXT_KEPT], tr.parse_instruction(text)[1]
    if tr.parse_instruction(head)[1] != kind:
        head += f" ... {kind}("
    mosaic = 'custom_call_target="tpu_custom_call"'
    return head + (f" ... {mosaic}" if mosaic in text else "")


def _slim_device(parts) -> bytes:
    """A device plane with every event and, of each event's metadata, the
    id, the first ``TEXT_KEPT`` characters of the instruction's text and
    the ``tf_op`` and ``program_id`` stats (``XEventMetadata``: ``id = 1,
    name = 2, stats = 5``; ``XStat.metadata_id = 1``)."""
    from benchmarks import gap_chain as gc

    stat_names = {}
    for f2, _, entry in parts:
        if f2 == 5:
            got = dict((f3, v) for f3, _, v in gc._fields(entry))
            meta = dict((f4, v) for f4, _, v in gc._fields(got[2]))
            stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
    body = bytearray()
    for f2, w2, v in parts:
        if f2 == 4:
            got = dict((f3, x) for f3, _, x in gc._fields(v))
            meta = bytearray()
            for f4, w4, x in gc._fields(got[2]):
                if f4 == 1:
                    meta += _varint((1 << 3) | 0) + _varint(x)
                elif f4 == 2:
                    meta += _field(2, _cut(bytes(x).decode(
                        errors="replace")).encode())
                elif f4 == 5:
                    sid = next(y for f5, _, y in gc._fields(x) if f5 == 1)
                    if stat_names.get(sid) in ("tf_op", "program_id"):
                        meta += _field(5, x)
            body += _field(4, _varint((1 << 3) | 0) + _varint(got[1])
                           + _field(2, meta))
        elif w2 == 2:
            body += _field(f2, v)
        else:
            body += _varint((f2 << 3) | w2) + _varint(v)
    return bytes(body)


def slim(src: str, dst: str) -> None:
    """Copy of an ``.xplane.pb`` with chip 0's plane (``_slim_device``)
    and, of the host's plane, only the events the chain reads: the program's spans,
    the benchmark's ``bench.*`` marks, the enqueue and the completion
    callback of each run (the runtime's other events are nine tenths of a
    trace). Fields are copied as bytes: ``XSpace.planes = 1``; ``XPlane``:
    ``name = 2, lines = 3, event_metadata = 4`` (a map: ``key = 1``,
    ``value.name = 2``); ``XLine.events = 4``; ``XEvent.metadata_id = 1``."""
    from benchmarks import gap_chain as gc

    keep_names = {gc.ENQUEUE, gc.COMPLETE}
    for spec in gc.CHAINS.values():
        keep_names |= set(spec["owners"]) | {spec["fence"]}
    with open(src, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for field, wire, plane in gc._fields(space):
        if field != 1 or wire != 2:
            continue
        parts = list(gc._fields(plane))
        name = next(bytes(v) for f2, _, v in parts if f2 == 2)
        if name == b"/device:TPU:0":
            out += _field(1, _slim_device(parts))
            continue
        if name != b"/host:CPU":
            continue
        keep_ids = set()
        for f2, _, entry in parts:
            if f2 != 4:
                continue
            got = dict((f3, v) for f3, _, v in gc._fields(entry))
            meta = dict((f4, v) for f4, _, v in gc._fields(got[2]))
            text = bytes(meta.get(2, b"")).decode(errors="replace")
            if text in keep_names or text.startswith("bench."):
                keep_ids.add(got[1])
        body = bytearray()
        for f2, w2, v in parts:
            if f2 == 3:                       # a line: filter its events
                line = bytearray()
                for f3, w3, v3 in gc._fields(v):
                    if f3 == 4:
                        mid = next(x for f4, _, x in gc._fields(v3)
                                   if f4 == 1)
                        if mid in keep_ids:
                            line += _field(4, v3)
                    elif w3 == 2:
                        line += _field(f3, v3)
                    else:
                        line += _varint((f3 << 3) | w3) + _varint(v3)
                body += _field(3, line)
            elif f2 == 4:                     # metadata of kept events only
                key = next(x for f3, _, x in gc._fields(v) if f3 == 1)
                if key in keep_ids:
                    body += _field(4, v)
            elif w2 == 2:
                body += _field(f2, v)
            else:
                body += _varint((f2 << 3) | w2) + _varint(v)
        out += _field(1, body)
    with open(dst, "wb") as f:
        f.write(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--serve", default="serve-pythia69b-decode-closed")
    ap.add_argument("--train", default="train-pythia69b-zero3-1chip")
    ap.add_argument("--serve-trace-seconds", type=float, default=0.12)
    ap.add_argument("--train-trace-seconds", type=float, default=0.7)
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the same code at a toy size on the CPU")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmarks import device as devmod
    from benchmarks import gap_chain, harness
    from benchmarks.manifest import load_cell, load_plugin

    device = devmod.describe(1, args.rehearse)
    out_dir = os.path.join("chiprun_out", "chain")
    os.makedirs(out_dir, exist_ok=True)
    reports = {}
    # the small one first: the training cell fills the chip
    for name, seconds in ((args.serve, args.serve_trace_seconds),
                          (args.train, args.train_trace_seconds)):
        cell = load_cell(name)
        deploy = dict(cell.deploy, trace_seconds=seconds)
        config = dict(cell.config)
        if cell.runner == "serve":
            config["as_run"] = dict(config["as_run"],
                                    serve={"num_hidden_layers": 2})
        cell = dataclasses.replace(cell, deploy=deploy, config=config)
        run = load_plugin("runners", cell.runner).run(
            cell, types.SimpleNamespace(seed=1, seconds=3.0, trace=1,
                                        rehearse=args.rehearse), device)
        kept = os.path.join(out_dir, f"v5e_chain_{cell.runner}.xplane.pb")
        slim(gap_chain.trace_file(run), kept)
        report = dict(gap_chain.analyse(run), bytes=os.path.getsize(kept),
                      window=run.trace.window,
                      failures=list(harness.FAILURES))
        reports[cell.runner] = report
        print(json.dumps({cell.runner: report}, default=str))
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(reports, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
