"""Perf-regression observatory: versioned bench schema, history store,
legacy-round recovery, round-to-round diffs, and the regression gate.

The modules in here import only the stdlib (no jax, no numpy) —
``bench.py`` pulls this in on every run and the parsers must keep
working on whatever is left of a broken round's output. The
``tools/bench-diff`` shim registers a stub parent package so even the
framework's own ``__init__`` (which DOES import jax) never runs when
you only need the observatory. Pieces:

* :mod:`~deepspeed_tpu.bench.schema`  — schema v2 + validator (``parsed``
  can never silently go null again)
* :mod:`~deepspeed_tpu.bench.history` — append-only
  ``bench_history/history.jsonl``
* :mod:`~deepspeed_tpu.bench.legacy`  — tolerant recovery of driver
  round artifacts (``BENCH_rNN.json`` tail blobs; r03 is a committed
  ``"parsed": null`` example)
* :mod:`~deepspeed_tpu.bench.diff`    — direction-aware metric diffs +
  per-phase span diffs with regression attribution
* :mod:`~deepspeed_tpu.bench.gate`    — 0/1/2 exit-code regression gate
* :mod:`~deepspeed_tpu.bench.cli`     — the ``bench-diff`` console entry
* ``python -m deepspeed_tpu.bench``   — recover / validate / history

Docs: README "Perf trajectory", docs/tutorials/bench-diff.md.
"""
from deepspeed_tpu.bench.diff import (
    diff_results,
    flatten_metrics,
    metric_direction,
    render_markdown,
    render_text,
)
from deepspeed_tpu.bench.gate import (
    GATE_ERROR,
    GATE_OK,
    GATE_REGRESSED,
    gate_enabled,
    gate_threshold,
    run_gate,
)
from deepspeed_tpu.bench.history import (
    append_record,
    history_path,
    latest_record,
    load_history,
    record_for_round,
    record_from_result,
)
from deepspeed_tpu.bench.legacy import (
    recover_from_text,
    recover_round_file,
    recover_rounds,
    upgrade_legacy_result,
)
from deepspeed_tpu.bench.schema import (
    RECORD_VERSION,
    SCHEMA_VERSION,
    SUPPORTED_SCHEMA_VERSIONS,
    normalize_entry_row,
    validate_record,
    validate_result,
)

__all__ = [
    "SCHEMA_VERSION", "RECORD_VERSION", "SUPPORTED_SCHEMA_VERSIONS",
    "validate_result", "validate_record", "normalize_entry_row",
    "recover_from_text", "recover_round_file", "recover_rounds",
    "upgrade_legacy_result",
    "load_history", "append_record", "latest_record", "record_for_round",
    "record_from_result", "history_path",
    "diff_results", "render_text", "render_markdown", "flatten_metrics",
    "metric_direction",
    "run_gate", "gate_enabled", "gate_threshold",
    "GATE_OK", "GATE_REGRESSED", "GATE_ERROR",
]
