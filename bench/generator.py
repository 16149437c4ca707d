"""The one traffic generator: part-id tuples for a mix's requests.

A mix file (``bench/traffic/<mix>.json``) names the population the parts
come from and how they are drawn; this module turns it and ``--seed`` into
a deterministic stream. The stream does not depend on timing: a closed loop
that consumes it sees the same requests in the same order on every run of
one seed, however many it gets through.

Mix keys read here:

  parts_per_request  parts whose node-induced union is one request
  population         "hot": a subset of ``hot_fraction`` of the parts,
                     drawn Zipf(``zipf_s``) by a rank order; the subset
                     and its order come from ``population_seed``, so
                     every run serves the same popularity (the same
                     sizes, as often) and ``--seed`` only orders the
                     arrivals; "all": every part, uniformly
  unique             no part tuple repeats within a run
"""
from __future__ import annotations

import numpy as np

__all__ = ["PartStream", "seeded_rng"]

_CHUNK = 4096


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    return np.random.default_rng([int(seed), int(stream)])


class PartStream:
    """Iterator of sorted part-id tuples, one per request."""

    def __init__(self, mix: dict, n_parts: int, seed: int):
        self.k = int(mix["parts_per_request"])
        self.unique = bool(mix.get("unique", False))
        if mix["population"] == "hot":
            rng = seeded_rng(mix["population_seed"], 1)
            n_hot = max(self.k, int(round(mix["hot_fraction"] * n_parts)))
            # the draw order is the popularity rank: members[0] is hottest
            self.members = rng.choice(n_parts, n_hot, replace=False)
            p = np.arange(1, n_hot + 1, dtype=np.float64) ** -float(
                mix["zipf_s"])
            self.p = p / p.sum()
        elif mix["population"] == "all":
            self.members = np.arange(n_parts)
            self.p = None
        else:
            raise ValueError(f"unknown population {mix['population']!r}")
        if len(self.members) < self.k:
            raise ValueError(f"{len(self.members)} parts cannot make a "
                             f"request of {self.k} distinct parts")
        self._rng = seeded_rng(seed, 2)
        self._buf: list = []
        self._seen: set = set()

    def _refill(self) -> None:
        draws = self._rng.choice(len(self.members), size=(_CHUNK, self.k),
                                 p=self.p)
        for row in draws:
            if len(set(row.tolist())) < self.k:
                continue  # a request is k distinct parts
            tup = tuple(sorted(int(self.members[i]) for i in row))
            if self.unique:
                if tup in self._seen:
                    continue
                self._seen.add(tup)
            self._buf.append(tup)
        self._buf.reverse()  # pop() from the end keeps the draw order

    def __iter__(self):
        return self

    def __next__(self) -> tuple:
        while not self._buf:
            self._refill()
        return self._buf.pop()
