"""The one traffic generator: every mix is a data file it reads.

A traffic file (``traffic/<name>.json``) gives:

* ``loop``: ``"open"`` (requests fall due on a fixed schedule at the
  cell's ``rate_rps``, whatever the server does) or ``"closed"``
  (``clients`` callers, each sending its next request the moment its
  previous answer is complete);
* ``prompt`` and ``output``: length distributions in tokens,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
* ``block``: requests per stratified block.

Work is fixed and the seed does not change it.  Request ``i`` belongs to
block ``i // block``; inside a block the prompt lengths, output lengths
and inter-arrival gaps are the distributions' quantiles at the block's
midpoints ``(j + 0.5) / block`` (the gaps those of an exponential at the
rate), each in one fixed order of its own.  So every seed sends the same
sizes and gaps in the same order; the seed draws the token ids (and the
weights).  With the order open to the seed, the p90 of TTFT swung by 20-40%
from seed to seed at four fifths of the knee on one TPU v5e, as each
order made its own bursts.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np

_PROMPT, _OUTPUT, _GAP, _TOKENS = 1, 2, 3, 4
#: seeds the fixed order of each block (the seed of a run does not)
_BASE = 20251013


def quantile(dist: dict, p: float) -> int:
    """Length at cumulative probability ``p`` of a length distribution."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        return min(hi, lo + int(p * (hi - lo + 1)))
    if dist["dist"] == "lognormal":
        x = float(dist["median"]) * math.exp(
            float(dist["sigma"]) * NormalDist().inv_cdf(p))
        return int(min(hi, max(lo, round(x))))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


@dataclasses.dataclass(frozen=True)
class Item:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    gap_s: float                # open loop: wait after the previous request


class Source:
    """Deterministic request stream of one traffic mix and one seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 rate_rps: Optional[float] = None):
        self.t = traffic
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.block = int(traffic.get("block", 64))
        if traffic["loop"] == "open":
            if not rate_rps or rate_rps <= 0:
                raise ValueError("an open-loop mix needs the cell's rate_rps")
        elif traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.rate = rate_rps
        self._perms: dict = {}

    @property
    def open_loop(self) -> bool:
        return self.t["loop"] == "open"

    @property
    def clients(self) -> int:
        return int(self.t.get("clients", 0))

    def _rank(self, stream: int, i: int) -> float:
        """Midpoint probability of request ``i``'s place in its block's
        fixed order for ``stream``."""
        b, j = divmod(i, self.block)
        key = (stream, b)
        if key not in self._perms:
            self._perms[key] = np.random.default_rng(
                [_BASE, stream, b]).permutation(self.block)
        return (int(self._perms[key][j]) + 0.5) / self.block

    def item(self, i: int) -> Item:
        plen = quantile(self.t["prompt"], self._rank(_PROMPT, i))
        rng = np.random.default_rng([self.seed, _TOKENS, i])
        prompt = rng.integers(1, self.vocab, plen, dtype=np.int32)
        gap = 0.0
        if self.open_loop:
            gap = -math.log(1.0 - self._rank(_GAP, i)) / self.rate
        return Item(i, prompt, quantile(self.t["output"],
                                        self._rank(_OUTPUT, i)), gap)

    def max_prompt(self) -> int:
        return int(self.t["prompt"]["max"])

    def max_output(self) -> int:
        return int(self.t["output"]["max"])
