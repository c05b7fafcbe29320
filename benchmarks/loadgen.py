"""The one general open-loop traffic generator.  A traffic mix is a data file
of parameters; this turns it and a seed into a schedule of requests, each
with the wall-clock instant it is *due* (seconds from the schedule's
origin), a prompt and an output length.

Arrivals are an open loop: a request is due on the schedule whether or not
earlier ones finished, and every latency is taken from the due instant, so
a stall is charged to the requests that waited behind it.

Arrivals are a Poisson process of the mix's rate, conditioned on its count:
a stretch of ``length_s`` seconds holds ``round(rate * length_s)`` requests
whose due instants are independent and uniform over the stretch, which is
what a Poisson process is once the number of its arrivals is known.  Bursts
and lulls come as a Poisson process has them (gaps are exponential, and
many short ones may follow each other); only the amount of work in a
window is fixed.  Lengths are the evenly spaced quantiles of the mix's
clipped log-normal distributions, in an order the seed draws, so every
seed offers the same set of sizes in another order and two seeds differ
by when the work arrives, not by how much of it there is.  The ramp before
the window and the window itself are scheduled apart.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Scheduled:
    index: int
    due_s: float            # from the schedule's origin
    prompt: List[int]
    max_new: int


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a log-normal, clipped to [lo, hi]."""
    from statistics import NormalDist
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def _stretch(mix: Dict, rng, start_s: float, length_s: float,
             vocab_size: int, first_index: int) -> List[Scheduled]:
    """``round(rate * length_s)`` requests due in [start, start + length)."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    n = max(1, int(round(float(mix["rate_per_s"]) * length_s)))
    due = start_s + np.sort(rng.uniform(0.0, length_s, n))
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    prompts = rng.permutation(_lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    outputs = rng.permutation(_lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    return [Scheduled(first_index + i, float(due[i]),
                      rng.integers(0, vocab_size, int(prompts[i])).tolist(),
                      int(outputs[i])) for i in range(n)]


def schedule(mix: Dict, seed: int, ramp_s: float, seconds: float,
             vocab_size: int) -> List[Scheduled]:
    """The ramp's requests, due in [0, ramp_s), then the window's, due in
    [ramp_s, ramp_s + seconds), in due order."""
    rng = np.random.default_rng(int(seed))
    ramp = _stretch(mix, rng, 0.0, ramp_s, vocab_size, 0) if ramp_s > 0 \
        else []
    return ramp + _stretch(mix, rng, ramp_s, seconds, vocab_size, len(ramp))
