"""Tolerant recovery of bench results from committed round artifacts.

The driver records each round as ``BENCH_rNN.json`` = ``{n, cmd, rc, tail,
parsed}`` where ``tail`` is the LAST ~2000 characters of the run's output
and ``parsed`` is the driver's attempt at reading the final JSON line.
When the bench line outgrew the tail window (r03) the line's FRONT was cut
off, ``json.loads`` failed, and the round's perf evidence became
``"parsed": null`` — write-only. A run killed at its time limit prints no
line at all. Of the artifacts this was written for, ``BENCH_r02.json``
(complete ``parsed``) and ``BENCH_r03.json`` (truncated tail) remain.

This module re-ingests those blobs: a complete line upgrades to schema v2
via :func:`upgrade_legacy_result`; a truncated line goes through a
fragment scanner (:func:`scan_outermost`) that walks every ``"key":``
position, ``raw_decode``\\ s the value, and keeps the outermost decodable
fragments — recovering whole suite entries, per-phase tables, and trailing
top-level fields even when the headline itself is gone. Keys whose front
was truncated (``dam_bert_large_fp16`` for
``zero2_fusedadam_bert_large_fp16``) are resolved by unique suffix match.

Everything here is stdlib-only and never raises on malformed input — a
recovery parser that crashes on the garbage it exists to read would be
the original bug with extra steps.
"""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.bench.schema import (
    RECORD_VERSION,
    SCHEMA_VERSION,
    SUPPORTED_SCHEMA_VERSIONS,
    normalize_entry_row,
    validate_result,
)

# top-level keys of the v1 flat result that belong to the HEADLINE row
HEADLINE_KEYS = (
    "metric", "value", "unit", "value_band", "vs_baseline",
    "baseline_tokens_per_sec", "baseline_citation",
    "model_tflops_per_sec_chip", "mfu", "peak_tflops",
    "matmul_ceiling_tflops", "vs_ceiling", "hardware_tflops_per_sec_chip",
    "vs_ceiling_hardware", "window_samples_tokens_per_sec", "loss",
    "n_chips", "tokens_per_sec_chip", "error",
)

#: every suite-entry name that has ever appeared in a committed round —
#: the resolver for exact and truncated-suffix matches. (Hardcoded rather
#: than imported from bench.py: bench.py imports THIS package, and the
#: committed history must stay readable even after entries are renamed.)
KNOWN_ENTRY_NAMES = (
    "headline",
    "zero3_llama_3b_adafactor",
    "fastgen_paged_splitfuse_gpt2",
    "fastgen_sla_poisson_gpt2",
    "moe_ulysses_moe_350m_bf16",
    "moe_1b_large_experts",
    "zero2_fusedadam_bert_large_fp16",
    "zero3_llama_750m_bf16",
    "autotp_inference_gpt2_generate",
    "offload_param_memory",
    "autotune_smoke",
    "comm_cpu_mesh_world8",
    "comm_bw_onchip",
    "comm_bw",
    "comm_busbw_cpu_mesh_world8",
    "pipeline_1f1b_cpu_mesh",
    "converge_real_text",
    "stability_2k_cpu_mesh",
)

_EXTRA_TOP_KEYS = ("budget_s", "total_runtime_s", "entry_elapsed_s",
                   "gate", "schema_version")

_KEY_RE = re.compile(r'"((?:[^"\\]|\\.)*)"\s*:\s*')
_LEAD_KEY_RE = re.compile(r'\s*([A-Za-z0-9_.\-/]*)"\s*:\s*')

#: headline keys that ALSO appear inside train-entry rows — on a
#: front-truncated line these are only attributable to the headline once
#: an unambiguous headline key has anchored the region (otherwise they
#: are some cut-off entry's internals masquerading as top-level)
AMBIGUOUS_HEADLINE_KEYS = frozenset(
    {"tokens_per_sec_chip", "model_tflops_per_sec_chip",
     "hardware_tflops_per_sec_chip", "mfu", "loss", "error",
     "window_samples_tokens_per_sec"})


def scan_outermost(text: str) -> List[Tuple[str, Any, int, int]]:
    """All outermost decodable ``"key": <value>`` fragments in ``text`` as
    ``(key, value, start, end)``. A fragment nested inside an
    already-decoded value is skipped (its parent carries it); fragments
    whose value is itself truncated simply fail to decode, letting their
    complete CHILDREN surface as outermost instead.

    A front-truncated line usually starts mid-key (``dam_bert_large_fp16":
    {...`` in BENCH_r03) — the opening quote is gone so the normal pattern
    can't see it, but the VALUE is complete and recoverable; it surfaces
    as a first fragment with the truncated key."""
    dec = json.JSONDecoder()
    out: List[Tuple[str, Any, int, int]] = []
    covered = -1
    lead = _LEAD_KEY_RE.match(text)
    if lead and not text.lstrip().startswith("{"):
        try:
            val, end = dec.raw_decode(text, lead.end())
            out.append((lead.group(1), val, 0, end))
            covered = end
        except ValueError:
            pass
    for m in _KEY_RE.finditer(text):
        if m.start() < covered:
            continue
        try:
            val, end = dec.raw_decode(text, m.end())
        except ValueError:
            continue
        out.append((m.group(1), val, m.start(), end))
        covered = end
    return out


def _match_entry_name(key: str, val: Any) -> Optional[str]:
    """Resolve a (possibly front-truncated) fragment key to a known suite
    entry name. Rows are dicts/lists; scalars are never entries."""
    if not isinstance(val, (dict, list)):
        return None
    if key in KNOWN_ENTRY_NAMES:
        return key
    if len(key) < 6:
        return None
    hits = [n for n in KNOWN_ENTRY_NAMES if n.endswith(key)]
    return hits[0] if len(hits) == 1 else None


def _match_headline_key(key: str, val: Any) -> Optional[str]:
    if key in HEADLINE_KEYS:
        return key
    if len(key) < 4 or isinstance(val, (dict, list)):
        return None
    hits = [k for k in HEADLINE_KEYS if k.endswith(key)]
    return hits[0] if len(hits) == 1 else None


def upgrade_legacy_result(parsed: Dict[str, Any]) -> Dict[str, Any]:
    """Upgrade a complete v1 (flat) bench result to schema v2. v2 input is
    returned unchanged. Idempotent."""
    if parsed.get("schema_version") in SUPPORTED_SCHEMA_VERSIONS:
        return parsed
    rest = dict(parsed)
    headline: Dict[str, Any] = {}
    for key in HEADLINE_KEYS:
        if key in rest:
            headline[key] = rest.pop(key)
    # v1 embedded the headline row's telemetry context at top level
    for key in ("telemetry", "trace_phases", "memory"):
        if key in rest:
            headline[key] = rest.pop(key)
    entries: Dict[str, Any] = {}
    elapsed = rest.pop("entry_elapsed_s", None) or {}
    for name, row in (rest.pop("configs", None) or {}).items():
        entries[name] = normalize_entry_row(row, elapsed.get(name))
    if "comm_bw" in rest:
        entries["comm_bw"] = normalize_entry_row(rest.pop("comm_bw"))
    result: Dict[str, Any] = {"schema_version": SCHEMA_VERSION}
    for key in ("metric", "value", "unit", "vs_baseline"):
        if key in headline:
            result[key] = headline[key]
    result["headline"] = headline
    result["entries"] = entries
    for key in ("budget_s", "total_runtime_s"):
        if key in rest:
            result[key] = rest.pop(key)
    if rest:
        result["extras"] = rest
    return result


def recover_from_text(text: str) -> Tuple[Dict[str, Any], List[str]]:
    """Recover a (possibly partial) schema-v2 result from raw bench output
    — a full stdout log, or a driver tail blob with the line's front cut
    off. Returns ``(result, notes)``; ``notes`` records what had to be
    guessed or dropped."""
    notes: List[str] = []
    lines = [ln for ln in (text or "").splitlines() if ln.strip()]
    # complete line first: the last parseable JSON-object line wins
    for line in reversed(lines):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and ("metric" in obj
                                      or "schema_version" in obj):
            return upgrade_legacy_result(obj), notes
    # truncated line: the most JSON-ish line carries the fragments
    candidate = max(lines, key=lambda ln: ln.count('":'), default="")
    frags = scan_outermost(candidate)
    front_truncated = not candidate.lstrip().startswith("{")
    # on a front-truncated line, the true top-level headline scalars lived
    # at the cut-off FRONT; ambiguous keys found mid-line belong to some
    # truncated entry until an unambiguous headline key anchors the region
    headline_anchored = not front_truncated
    seen_entry = False
    headline: Dict[str, Any] = {}
    entries: Dict[str, Any] = {}
    extras: Dict[str, Any] = {}
    for key, val, _start, _end in frags:
        if key == "configs" and isinstance(val, dict):
            for name, row in val.items():
                entries[name] = normalize_entry_row(row)
            seen_entry = True
            continue
        entry_name = _match_entry_name(key, val)
        if entry_name is not None:
            entries[entry_name] = normalize_entry_row(val)
            seen_entry = True
            if entry_name != key:
                notes.append(f"entry key {key!r} resolved to "
                             f"{entry_name!r} by suffix")
            continue
        if key in ("telemetry", "trace_phases") and isinstance(val, dict):
            headline[key] = val
            continue
        if key in _EXTRA_TOP_KEYS:
            extras[key] = val
            continue
        head_key = _match_headline_key(key, val)
        if head_key is not None:
            if head_key in AMBIGUOUS_HEADLINE_KEYS \
                    and (not headline_anchored or seen_entry):
                notes.append(f"fragment {key!r} dropped: inside a "
                             "truncated entry, not attributable to the "
                             "headline")
                continue
            headline[head_key] = val
            if head_key not in AMBIGUOUS_HEADLINE_KEYS:
                headline_anchored = True
            if head_key != key:
                notes.append(f"headline key {key!r} resolved to "
                             f"{head_key!r} by suffix")
            continue
        notes.append(f"unrecognized fragment {key!r} dropped")
    if not frags:
        notes.append("no JSON fragments found in output")
    result: Dict[str, Any] = {"schema_version": SCHEMA_VERSION}
    for key in ("metric", "value", "unit", "vs_baseline"):
        if key in headline:
            result[key] = headline[key]
    result["headline"] = headline
    result["entries"] = entries
    elapsed = extras.pop("entry_elapsed_s", None) or {}
    for name, secs in elapsed.items() if isinstance(elapsed, dict) else ():
        if name in entries and "elapsed_s" not in entries[name]:
            entries[name]["elapsed_s"] = secs
    for key in ("budget_s", "total_runtime_s"):
        if key in extras:
            result[key] = extras.pop(key)
    if extras:
        result["extras"] = extras
    return result, notes


def round_id_from_path(path: str) -> str:
    m = re.search(r"(r\d+)", os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def recover_round_file(path: str) -> Dict[str, Any]:
    """Re-ingest one committed ``BENCH_rNN.json`` driver artifact into a
    bench_history record. Uses ``parsed`` when the driver managed to read
    the line; otherwise recovers what the tail still holds. An artifact
    that is itself corrupt JSON (the damage class this parser exists
    for) degrades to raw-text recovery, never a raise."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    round_id = round_id_from_path(path)
    source = os.path.basename(path)
    try:
        data = json.loads(text)
    except ValueError:
        data = None
    if not isinstance(data, dict):
        result, notes = recover_from_text(text)
        notes.append("artifact not a JSON object; recovered from raw text")
        return {
            "record_version": RECORD_VERSION,
            "round": round_id,
            "source": source,
            "rc": None,
            "recovered": True,
            "complete": not validate_result(result),
            "result": result,
            "notes": notes,
        }
    return recover_round_data(data, round_id, source)


def recover_round_data(data: Dict[str, Any], round_id: str,
                       source: str) -> Dict[str, Any]:
    """Same as :func:`recover_round_file` for an already-loaded artifact
    dict (``{n, cmd, rc, tail, parsed}``)."""
    notes: List[str] = []
    rc = data.get("rc")
    parsed = data.get("parsed")
    if isinstance(parsed, dict):
        result = upgrade_legacy_result(parsed)
        recovered = False
    else:
        result, notes = recover_from_text(data.get("tail") or "")
        recovered = True
        if rc not in (0, None):
            notes.append(f"round exited rc={rc}")
    complete = not validate_result(result)
    return {
        "record_version": RECORD_VERSION,
        "round": round_id,
        "source": source,
        "rc": rc,
        "recovered": recovered,
        "complete": complete,
        "result": result,
        "notes": notes,
    }


def recover_rounds(root: str) -> List[Dict[str, Any]]:
    """Recover every ``BENCH_r*.json`` under ``root``, ordered by round."""
    paths = sorted(
        os.path.join(root, name) for name in os.listdir(root)
        if re.fullmatch(r"BENCH_r\d+\.json", name))
    return [recover_round_file(p) for p in paths]
