"""Closed loop: ``clients`` callers, each sending its next request the
moment its last one completed (an evaluation harness, RLHF roll-outs).
A slow server receives less load, so latencies here say little; the
tokens completed per second are what is judged.

Each client draws its lengths from its own seeded stream of stratified
blocks (see ``common.stratified_lengths``), so a seed fixes what every
client will ask for however the server interleaves them.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.generators.common import (Request, fit_prompt, prompt_tokens,
                                          stratified_lengths)

_BLOCK = 16


class ClosedLoop:
    closed = True

    def __init__(self, params: Dict[str, Any], seed: int, vocab: int,
                 max_len: int, start_s: float):
        self.params, self.seed = params, seed
        self.vocab, self.max_len = vocab, max_len
        self.clients = int(params["clients"])
        self._rng = [np.random.default_rng([seed, 1, c])
                     for c in range(self.clients)]
        self._queue: List[List[tuple]] = [[] for _ in range(self.clients)]
        self._uid = 0
        self._stopped = False
        # every client starts at the start of the pre-roll
        self._pending = [self._make(c, start_s) for c in range(self.clients)]

    def _lengths(self, client: int) -> tuple:
        if not self._queue[client]:
            rng = self._rng[client]
            p = stratified_lengths(self.params["prompt_tokens"], _BLOCK, rng)
            o = stratified_lengths(self.params["output_tokens"], _BLOCK, rng)
            self._queue[client] = list(zip(p.tolist(), o.tolist()))
        return self._queue[client].pop()

    def _make(self, client: int, due: float) -> Request:
        p, o = self._lengths(client)
        uid, self._uid = self._uid, self._uid + 1
        n = fit_prompt(p, o, self.max_len)
        return Request(uid, due, prompt_tokens(self.seed, uid, n, self.vocab),
                       o, client=client)

    def due(self, now: float) -> List[Request]:
        out = [r for r in self._pending if r.due <= now]
        self._pending = [r for r in self._pending if r.due > now]
        return out

    def next_due(self) -> Optional[float]:
        return min((r.due for r in self._pending), default=None)

    def on_complete(self, request: Request, now: float) -> None:
        """The client's next request is due the instant this one ended."""
        if not self._stopped:
            self._pending.append(self._make(request.client, now))

    def stop(self) -> None:
        self._stopped = True
        self._pending = []


def build(params: Dict[str, Any], seed: int, vocab: int, max_len: int,
          start_s: float, end_s: float) -> ClosedLoop:
    return ClosedLoop(params, seed, vocab, max_len, start_s)
