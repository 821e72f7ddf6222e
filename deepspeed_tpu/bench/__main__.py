"""``python -m deepspeed_tpu.bench`` — history maintenance subcommands.

* ``recover``  — re-ingest committed ``BENCH_r*.json`` round artifacts
  into ``bench_history/history.jsonl`` (skips rounds already recorded)
* ``validate`` — validate a bench result / history file against the
  versioned schema (exit 0 valid, 1 invalid, 2 error)
* ``history``  — print the recorded trajectory as a table

``bench-diff`` (round-to-round comparison) is its own console entry:
``deepspeed_tpu.bench.cli``.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from deepspeed_tpu.bench import history as history_mod
from deepspeed_tpu.bench import legacy
from deepspeed_tpu.bench.schema import validate_record, validate_result


def _cmd_recover(args) -> int:
    root = args.repo or history_mod.default_repo_root()
    records = legacy.recover_rounds(root)
    if not records:
        print(f"recover: no BENCH_r*.json under {root}", file=sys.stderr)
        return 1
    existing, _ = history_mod.load_history(args.history)
    seen = {rec.get("round") for rec in existing}
    wrote = 0
    for rec in records:
        if rec["round"] in seen and not args.force:
            print(f"recover: {rec['round']} already in history, skipped")
            continue
        bad = validate_record(rec)
        if bad:
            print(f"recover: {rec['round']} produced an invalid record: "
                  f"{bad[0]}", file=sys.stderr)
            return 2
        path = history_mod.append_record(rec, args.history)
        wrote += 1
        status = "complete" if rec["complete"] else "partial"
        how = "recovered from tail" if rec["recovered"] else "from parsed"
        n_entries = len(rec["result"].get("entries") or {})
        head = rec["result"].get("headline") or {}
        val = head.get("value")
        print(f"recover: {rec['round']} -> {path} [{status}, {how}; "
              f"headline={'%.1f' % val if isinstance(val, (int, float)) else 'lost'}, "
              f"{n_entries} entries]")
    print(f"recover: wrote {wrote} record(s)")
    return 0


def _cmd_validate(args) -> int:
    try:
        with open(args.file, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        print(f"validate: {e}", file=sys.stderr)
        return 2
    if args.file.endswith(".jsonl"):
        errs: List[str] = []
        for i, line in enumerate(text.splitlines(), 1):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                errs.append(f"line {i}: unparseable")
                continue
            errs += [f"line {i}: {e}" for e in validate_record(rec)]
    else:
        try:
            obj = json.loads(text)
        except ValueError:
            # a raw bench stdout log: validate its recovered final line
            obj, _ = legacy.recover_from_text(text)
        errs = (validate_record(obj)
                if isinstance(obj, dict) and "record_version" in obj
                else validate_result(obj))
    for e in errs:
        print(f"validate: {e}")
    print(f"validate: {'OK' if not errs else f'{len(errs)} error(s)'}")
    return 0 if not errs else 1


def _cmd_history(args) -> int:
    records, notes = history_mod.load_history(args.history)
    if not records:
        print("history: empty (run `python -m deepspeed_tpu.bench "
              "recover` to ingest committed rounds)")
        return 0
    print(f"{'round':8s} {'headline':>12s} {'mfu':>6s} {'vs_base':>8s} "
          f"{'entries':>7s}  status")
    for rec in records:
        result = rec.get("result") or {}
        head = result.get("headline") or {}
        val = head.get("value")
        mfu = head.get("mfu")
        vsb = head.get("vs_baseline")
        best = head.get("best_row") or {}
        status = ("complete" if rec.get("complete") else
                  "partial" if (result.get("entries") or head) else "lost")
        if rec.get("recovered"):
            status += ",recovered"
        if rec.get("rc") not in (0, None):
            status += f",rc={rec['rc']}"
        note = (f" best={best.get('name')}@mfu{best.get('mfu')}"
                if best.get("name") else "")
        print(f"{rec.get('round', '?'):8s} "
              f"{val if val is not None else '—':>12} "
              f"{mfu if mfu is not None else '—':>6} "
              f"{vsb if vsb is not None else '—':>8} "
              f"{len(result.get('entries') or {}):>7d}  {status}{note}")
    for note in notes:
        print(f"history: note: {note}", file=sys.stderr)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.bench",
        description="bench history maintenance (recover / validate / "
                    "history); see also the bench-diff CLI")
    sub = p.add_subparsers(dest="cmd", required=True)
    pr = sub.add_parser("recover",
                        help="ingest committed BENCH_r*.json into history")
    pr.add_argument("--repo", default=None,
                    help="checkout root (default: this package's parent)")
    pr.add_argument("--history", default=None,
                    help="history dir or .jsonl (default: bench_history/)")
    pr.add_argument("--force", action="store_true",
                    help="re-append rounds already in history")
    pv = sub.add_parser("validate",
                        help="validate a result/record/.jsonl file")
    pv.add_argument("file")
    ph = sub.add_parser("history", help="print the recorded trajectory")
    ph.add_argument("--history", default=None)
    args = p.parse_args(argv)
    return {"recover": _cmd_recover,
            "validate": _cmd_validate,
            "history": _cmd_history}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
