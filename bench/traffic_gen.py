"""The traffic generator: a mix is a data file, `bench/traffic/<mix>.json`,
whose ``generator`` key names the module that turns its parameters, with
the run's seed, into requests: `bench/traffic/<generator>.py`. Mixes of
one kind differ only in their data; a new kind of arrivals comes as a new
module beside the others.

A generator module defines ``Traffic(mix, *, vocab, max_len, clients,
page, seed)``, and the harness calls only these:

* ``opening()`` -> prompts that set-up prefills (one token each) before
  the traffic starts, such as the histories that sessions resume;
* ``poll(now)`` -> the `Request`s to submit at host time ``now``;
* ``finished(request, tokens)`` -> one request served its ``tokens``.

Every seed gets the same sizes and gaps in the same order, drawn once
from `SHAPE_SEED`; the run's seed draws every token id (and the
weights). The engine's schedule depends only on lengths, so every seed
does the same work on different numbers, and runs differ only by the
chip's and the host's own variation. Ids are drawn from the
configuration's vocabulary rows.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import NamedTuple

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
SHAPE_SEED = 20240717       # fixes the sizes, and their order, for every seed
POOL = 4096                 # values drawn for each length or gap


class Request(NamedTuple):
    """One request as a generator hands it to the harness."""
    prompt: np.ndarray      # int32 ids
    budget: int             # tokens to decode, greedily
    cached: int             # leading prompt tokens the prefix cache holds
    client: object          # the generator's own, handed back to finished()


def load(mix: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{mix}.json").read_text())


def make(mix: dict, **kw):
    """The generator `mix` names, built with the run's parameters."""
    mod = importlib.import_module(f"bench.traffic.{mix['generator']}")
    return mod.Traffic(mix, **kw)


def shape_rng() -> np.random.Generator:
    """The generator every seed shares for sizes and gaps."""
    return np.random.default_rng(SHAPE_SEED)


def id_rng(seed: int) -> np.random.Generator:
    """The run's generator of token ids."""
    return np.random.default_rng([int(seed), 2])


def uniform(lo_hi, rng, n: int = POOL) -> np.ndarray:
    """`n` integers drawn uniformly from ``[lo, hi]``, both included."""
    lo, hi = lo_hi
    return rng.integers(lo, hi + 1, n)


class Cycle:
    """A fixed sequence of values, visited in order."""

    def __init__(self, values: np.ndarray):
        self.values = values
        self.i = 0

    def next(self):
        v = self.values[self.i % len(self.values)]
        self.i += 1
        return v
