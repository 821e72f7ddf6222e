"""Open loop: independent users. Requests are due on a schedule fixed
before the run, whatever the server does.

One law. Arrivals are a Poisson process of the mix's ``rate_per_s``
conditioned on its counts: each stratum of ``stratum_s`` seconds holds
exactly ``rate_per_s * stratum_s`` arrivals at independent uniform
instants (given its count, that is what a Poisson process is: bursts and
lulls included), and the stratum's prompt and answer lengths are the
evenly spaced quantiles of their distributions in a random order. So
every stratum offers the same requests, and the draw decides when each
comes and which length meets which burst.

The schedule (instants and lengths) is drawn from the mix's
``schedule_seed``, stratum by stratum, so it is the same in every run: a
recorded draw of the law, replayed, as a serving benchmark replays a
trace. The run's ``--seed`` draws the tokens (and, in the runner, the
weights). Why: with the schedule drawn afresh from ``--seed`` the p90 of
the time to first token spread by 10-19 % between seeds at 30 s (PERF.md,
PR 22): the tail is made by the two or three worst bursts of a window, and
no bound the contract admits (at most 0.1, spread under half of it) can
hold a metric that moves so much with the draw. A mix without
``schedule_seed`` draws the schedule from ``--seed`` (the knee sweep does,
to see the law and not one draw of it).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.generators.common import (Request, fit_prompt, prompt_tokens,
                                          stratified_lengths)


class OpenLoop:
    closed = False

    def __init__(self, requests: List[Request]):
        self.requests = requests       # by due time
        self._next = 0

    def due(self, now: float) -> List[Request]:
        out = []
        while self._next < len(self.requests) \
                and self.requests[self._next].due <= now:
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.requests):
            return self.requests[self._next].due
        return None

    def on_complete(self, request: Request, now: float) -> None:
        pass

    def stop(self) -> None:
        self._next = len(self.requests)


def build(params: Dict[str, Any], seed: int, vocab: int, max_len: int,
          start_s: float, end_s: float) -> OpenLoop:
    """Requests due in ``[start_s, end_s)`` (0 = start of the window).
    Strata are laid from the start of the window, backwards over the
    pre-roll and forwards over the tail; each is drawn from its own
    stream, so the schedule does not depend on the stretch asked for."""
    s = float(params["stratum_s"])
    per = int(round(params["rate_per_s"] * s))
    schedule_seed = params.get("schedule_seed")
    if schedule_seed is None:
        schedule_seed = seed
    requests: List[Request] = []
    for k in range(math.floor(start_s / s), math.ceil(end_s / s)):
        rng = np.random.default_rng([int(schedule_seed), 0, k % 2 ** 32])
        dues = np.sort(rng.uniform(k * s, (k + 1) * s, per))
        prompts = stratified_lengths(params["prompt_tokens"], per, rng)
        outs = stratified_lengths(params["output_tokens"], per, rng)
        for i, (due, p, o) in enumerate(zip(dues, prompts, outs)):
            if start_s <= due < end_s:
                uid = (k % 2 ** 16) * per + i
                n = fit_prompt(int(p), int(o), max_len)
                requests.append(Request(uid, float(due), prompt_tokens(
                    seed, uid, n, vocab), int(o)))
    return OpenLoop(requests)
