"""From a configuration file to the program's model config, through the
program's own importer (``hf_import.config_from_hf``), as a user with a
Hugging Face ``config.json`` would go. The importer only reads
attributes, so it is handed the file's keys as a plain namespace and not a
``transformers`` config object: importing ``transformers`` cost 14 s of
every run's set-up (my chip run, PR 22, call 4), and a test holds the two
to the same result. ``--rehearse`` swaps in the toy sizes named in the
file.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

#: keys of a configuration file that are the benchmark's, not the source's
OWN_KEYS = ("source", "published", "as_run", "assumed", "compute_dtype",
            "reference", "flops", "notes", "rehearse")


def hf_kwargs(config: Dict[str, Any], role: str) -> Dict[str, Any]:
    """The source's keys as this role runs them (``model_type`` included)."""
    kw = {k: v for k, v in config.items() if k not in OWN_KEYS}
    kw.update(config["as_run"][role])
    return kw


def build(config: Dict[str, Any], role: str, remat: str = "none",
          rehearse: bool = False):
    """``role`` is ``train`` or ``serve`` (the depth differs by role)."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    kw = hf_kwargs(config, role)
    if rehearse:
        kw.update(config["rehearse"])
    return dataclasses.replace(config_from_hf(types.SimpleNamespace(**kw)),
                               dtype=config["compute_dtype"], remat=remat)
