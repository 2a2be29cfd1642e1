"""The one traffic generator: a traffic file's parameters -> each call's mixes.

A traffic file (`perfbench/traffic/<name>.json`) names the entry point that
serves it and how its calls draw applications:

  entry            module under `perfbench/entries/` that makes the call
  designs          design names the call simulates
  cycles           simulated cycles per row
  mixes_per_call   candidate mixes (grid rows before solos) in one call
  apps_per_mix     [low, high]: apps in each mix, drawn uniformly
  distinct         "call": every app of a call differs (a sweep's solo rows
                   are its distinct apps, so the row count is fixed);
                   "mix": apps differ within each mix
  same_every_call  true: draw once and repeat it (one user's simulation)

Apps come from the paper's section 6 rule: Table 2 benches, leaving out the
low-low class (LUD, NN). Everything is drawn from the seed alone, so the
same seed gives the same calls, and every seed gives calls of the same
shape.
"""
from __future__ import annotations

import numpy as np


def eligible(cfg) -> list:
    """Benches the section 6 rule may draw: all but the low-low class."""
    return sorted(b for b in cfg["apps"]
                  if cfg["app_category"][b] != "low-low")


class Traffic:
    def __init__(self, spec: dict, cfg: dict, seed: int):
        self.spec = spec
        self.pool = eligible(cfg)
        self.rng = np.random.default_rng(seed)
        self._fixed = self._draw() if spec.get("same_every_call") else None

    def _draw(self) -> list:
        lo, hi = self.spec["apps_per_mix"]
        sizes = [int(n) for n in self.rng.integers(
            lo, hi + 1, self.spec["mixes_per_call"])]
        if self.spec["distinct"] == "call":
            if sum(sizes) > len(self.pool):
                raise ValueError(f"{sum(sizes)} distinct apps asked of a "
                                 f"pool of {len(self.pool)}")
            apps = [str(b) for b in self.rng.permutation(self.pool)]
            out, at = [], 0
            for n in sizes:
                out.append(tuple(apps[at:at + n]))
                at += n
            return out
        return [tuple(str(b) for b in self.rng.choice(self.pool, n,
                                                      replace=False))
                for n in sizes]

    def draw(self) -> list:
        """The mixes of the next call: a list of bench-name tuples."""
        return list(self._fixed) if self._fixed is not None else self._draw()
