"""The one traffic generator: reads a mix file ``bench/traffic/<mix>.json``.

A mix states its length distributions and how requests arrive; nothing
else about a mix lives in code.  Sizes are stratified: every block of
``STRATA`` consecutive requests holds the same ``STRATA`` quantiles of each
length distribution (the midpoints of equal-probability strata), in an
order drawn from the seed.  So every seed serves the same set of sizes in
another order, and a window that covers a few blocks sees nearly the same
work whatever the seed.  Prompt token ids are uniform over the
configuration's vocabulary.  Request ``i`` depends only on the seed and
``i``: a longer trace extends a shorter one.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

ARRIVALS = ("all_at_start",)
STRATA = 16  # requests per block of stratified sizes


@dataclass(frozen=True)
class Dist:
    """A length distribution: ``lognormal`` (truncated to [min, max], with
    the median and the sigma of the untruncated log), ``uniform`` integers
    in [min, max], or ``fixed`` at ``value``."""

    kind: str
    min: int
    max: int
    median: float = 0.0
    sigma: float = 0.0

    @classmethod
    def from_json(cls, d: dict) -> "Dist":
        kind = d["kind"]
        if kind == "fixed":
            return cls(kind, int(d["value"]), int(d["value"]))
        if kind == "uniform":
            return cls(kind, int(d["min"]), int(d["max"]))
        if kind == "lognormal":
            return cls(kind, int(d["min"]), int(d["max"]), float(d["median"]),
                       float(d["sigma"]))
        raise ValueError(f"unknown length distribution {kind!r}")

    def quantile(self, u: float) -> int:
        """The length at probability ``u`` in (0, 1)."""
        if self.kind == "fixed":
            return self.min
        if self.kind == "uniform":
            return min(self.max, self.min + int((self.max - self.min + 1) * u))
        nd = NormalDist()
        mu = math.log(self.median)
        lo = nd.cdf((math.log(self.min) - mu) / self.sigma)
        hi = nd.cdf((math.log(self.max) - mu) / self.sigma)
        x = math.exp(mu + self.sigma * nd.inv_cdf(lo + (hi - lo) * u))
        return int(min(self.max, max(self.min, round(x))))

    def strata(self, k: int) -> list[int]:
        """The ``k`` stratum midpoints, in increasing order."""
        return [self.quantile((j + 0.5) / k) for j in range(k)]


@dataclass(frozen=True)
class Mix:
    name: str
    arrival: str
    prompt: Dist
    output: Dist
    block_size: int

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        arrival = d["arrival"]["kind"]
        if arrival not in ARRIVALS:
            raise ValueError(f"{path}: unknown arrival kind {arrival!r}")
        return cls(name=Path(path).stem, arrival=arrival,
                   prompt=Dist.from_json(d["prompt_len"]),
                   output=Dist.from_json(d["output_len"]),
                   block_size=int(d["block_size"]))

    @property
    def max_total_len(self) -> int:
        """The longest context a server for this mix admits: the longest
        prompt and the longest answer the distributions allow."""
        return self.prompt.max + self.output.max

    def prompt_lengths(self) -> list[int]:
        return self.prompt.strata(STRATA)

    def output_lengths(self) -> list[int]:
        return self.output.strata(STRATA)

    def sizes(self, seed: int, n: int) -> list[tuple[int, int]]:
        """(prompt length, new tokens) of the first ``n`` requests."""
        rng = np.random.default_rng([seed, 0])
        ps, gs = self.prompt_lengths(), self.output_lengths()
        out: list[tuple[int, int]] = []
        while len(out) < n:
            pi, gi = rng.permutation(STRATA), rng.permutation(STRATA)
            out += [(ps[a], gs[b]) for a, b in zip(pi, gi)]
        return out[:n]

    def requests(self, seed: int, n: int, vocab: int) -> list[tuple[np.ndarray, int]]:
        """(prompt token ids, new tokens) of the first ``n`` requests."""
        rng = np.random.default_rng([seed, 1])
        out = []
        for p, g in self.sizes(seed, n):
            out.append((rng.integers(0, vocab, size=p, dtype=np.int32), g))
        return out
