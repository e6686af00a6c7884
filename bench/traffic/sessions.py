"""Closed-loop conversational sessions, one client per batch row, no think
time (choosing-metrics guide, section 5).

A session starts from a history of ``history_frac`` x max_len unique
tokens, which set-up prefills. Each turn sends the history plus
``new_tokens`` fresh tokens and decodes ``output_tokens`` greedily, with
no stop condition; the next turn's history is that prompt plus its
output, so every full page of it is in the prefix cache. A session whose
next turn would pass max_len ends, and a fresh one takes its client.
"""
from __future__ import annotations

import numpy as np

from bench.traffic_gen import (POOL, Cycle, Request, id_rng, shape_rng,
                               uniform)


class _Client:
    def __init__(self, history: np.ndarray):
        self.history = history
        self.cached = 0             # history tokens the prefix cache holds


class Traffic:
    def __init__(self, mix: dict, *, vocab: int, max_len: int, clients: int,
                 page: int, seed: int):
        self.vocab, self.max_len, self.page = vocab, max_len, page
        self.n_clients = clients
        shape = shape_rng()
        self.tokens = id_rng(seed)
        self.outputs = Cycle(uniform(mix["output_tokens"], shape))
        lo, hi = mix["history_frac"]
        self.histories = Cycle(np.floor(shape.uniform(lo, hi, POOL)
                                        * max_len).astype(np.int64))
        self.news = Cycle(uniform(mix["new_tokens"], shape))
        self.clients: list[_Client] = []
        self.due: list[Request] = []

    def ids(self, n: int) -> np.ndarray:
        return self.tokens.integers(0, self.vocab, int(n)).astype(np.int32)

    def history(self) -> np.ndarray:
        """A fresh session's starting history."""
        return self.ids(self.histories.next())

    def turn(self, history: np.ndarray) -> tuple[np.ndarray, int] | None:
        """The next turn after `history`: (prompt, decode budget), or None
        when it would pass max_len and the session ends."""
        new, out = int(self.news.next()), int(self.outputs.next())
        if len(history) + new + out > self.max_len:
            return None
        return np.concatenate([history, self.ids(new)]), out

    def _queue_next(self, c: _Client):
        nxt = self.turn(c.history)
        if nxt is None:                    # the session ends: a new one
            c.history, c.cached = self.history(), 0
            nxt = self.turn(c.history)
        prompt, budget = nxt
        self.due.append(Request(prompt, budget, c.cached, c))

    def _resumed(self, c: _Client, history: np.ndarray):
        c.history = history
        c.cached = (len(history) // self.page) * self.page
        self._queue_next(c)

    def opening(self) -> list[np.ndarray]:
        """Every client's starting history; each one's first turn is due
        as soon as the traffic starts."""
        self.clients = [_Client(self.history())
                        for _ in range(self.n_clients)]
        for c in self.clients:
            self._resumed(c, c.history)
        return [c.history for c in self.clients]

    def poll(self, now: float) -> list[Request]:
        due, self.due = self.due, []
        return due

    def finished(self, req: Request, tokens: np.ndarray):
        self._resumed(req.client, np.concatenate([req.prompt, tokens]))
