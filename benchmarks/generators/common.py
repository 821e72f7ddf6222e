"""What every traffic generator shares: the request record, seeded prompt
tokens, and draws from clipped length distributions with the work fixed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    uid: int
    due: float                 # seconds from the start of the window
    prompt: List[int]
    max_new: int
    client: Optional[int] = None   # closed loop: whose request this is


def prompt_tokens(seed: int, uid: int, n: int, vocab: int) -> List[int]:
    """Seeded and unshared: no two requests have a common prefix beyond
    chance."""
    return np.random.default_rng([seed, uid]).integers(
        0, vocab, n).tolist()


def stratified_lengths(spec: Dict[str, Any], n: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths that are the distribution's own ``n`` evenly spaced
    quantiles, in an order drawn from ``rng``: every seed offers the same
    multiset of lengths, so the same work. ``spec``: ``{"dist":
    "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
    "uniform", "min", "max"}``."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)
    return rng.permutation(vals)


def fit_prompt(n: int, max_new: int, max_len: int) -> int:
    """Keep prompt + answer inside the engine's position range."""
    return max(1, min(n, max_len - max_new - 1))
