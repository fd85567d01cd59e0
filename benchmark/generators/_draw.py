"""Draws shared by the generators.

Copied from `dynamo_tpu/loadgen/trace.py` (`_length`, `_arrivals`: lognormal
lengths clipped to [min, max], exponential gaps), so that a later PR may
change the program's load generator and not the yardstick. Corrected: the
original draws every length and gap from the seed, so two seeds give two
different amounts of work and a run-to-run spread that is the seed's, not the
system's. Here a mix fixes ONE set of lengths and ONE set of gaps (the
distribution's own quantiles at (i + 0.5) / n) in ONE order (shuffled once by
the mix's `order_seed`), and the run's seed only rotates that order (it starts
the same cycle at another place) and draws the token ids. A full reshuffle per
seed was tried on paper and dropped: where the long prompts clump decides the
tails, so it would make the tail of a 40 s window the seed's.
"""

from __future__ import annotations

import math
import random
import statistics

import numpy as np


def lognormal_set(n: int, spec: dict) -> list:
    """n lengths: the quantiles of lognormal(median, sigma), clipped."""
    dist = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    return [max(spec["min"], min(spec["max"], int(round(math.exp(dist.inv_cdf((i + 0.5) / n))))))
            for i in range(n)]


def uniform_set(n: int, lo: int, hi: int) -> list:
    """n lengths spread evenly over [lo, hi]."""
    return [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]


def exponential_gaps(n: int, rate: float) -> list:
    """n gaps: the quantiles of Exp(rate); their mean is 1 / rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def shuffled(items: list, rng: random.Random) -> list:
    out = list(items)
    rng.shuffle(out)
    return out


def rotated(items: list, seed: int) -> list:
    k = seed % len(items)
    return items[k:] + items[:k]


def token_ids(seed: int, stream: int, n: int, vocab: int) -> list:
    """n ids in [3, vocab): past the tokenizer's three special ids."""
    rng = np.random.Generator(np.random.SFC64(np.random.SeedSequence([seed, stream])))
    return rng.integers(3, vocab, n, dtype=np.int64).tolist()
