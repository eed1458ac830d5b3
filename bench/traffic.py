"""The one traffic generator: every traffic file is data that this module
turns into work, seeded.

Sizes and gaps are fixed sets drawn at evenly spaced quantiles of the
traffic's distributions, so every seed offers the same work; the seed
only orders them and draws the token ids.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (2 ** 63), stream])


def token_rows(seed: int, stream: int, n: int, length: int,
               vocab: int) -> np.ndarray:
    """``n`` seeded rows of uniform token ids, all rows different."""
    return rng(seed, stream).integers(0, vocab, (n, length), dtype=np.int32)


def lognormal_sizes(spec: dict, n: int) -> list[int]:
    """``n`` sizes at the quantiles (i + 0.5) / n of a lognormal with the
    spec's ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    norm = statistics.NormalDist()
    out = []
    for i in range(n):
        z = norm.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def exponential_gaps(rate: float, n: int) -> list[float]:
    """``n`` Poisson inter-arrival gaps at the quantiles (i + 0.5) / n."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def requests(traffic: dict, seed: int, n: int, vocab: int,
             seconds: float = 0.0) -> list[dict]:
    """``n`` requests: prompt ids and output budget.  For an open loop each
    also gets ``due``, seconds from the window's start: the first
    ``rate * seconds`` arrive inside the window, their gaps scaled to fill
    it exactly, and the rest keep the load on after it."""
    r = rng(seed, 1)
    n_win = (min(window_count(traffic, seconds), n)
             if traffic["loop"] == "open" else n)
    out = []
    for part in (n_win, n - n_win):       # the window's set, then the rest
        plens = lognormal_sizes(traffic["prompt"], part)
        olens = lognormal_sizes(traffic["output"], part)
        out += [{"prompt": r.integers(0, vocab, plens[i]).tolist(),
                 "max_new_tokens": olens[j]}
                for i, j in zip(r.permutation(part), r.permutation(part))]
    if traffic["loop"] == "open":
        rate = traffic["rate_per_s"]
        win = exponential_gaps(rate, n_win)
        win = [g * seconds / sum(win) for g in win]
        rest = exponential_gaps(rate, max(n - n_win, 1))
        gaps = ([win[j] for j in r.permutation(n_win)]
                + [rest[j] for j in r.permutation(len(rest))])
        t = 0.0
        for req, g in zip(out, gaps):
            t += g
            req["due"] = t
    return out


def window_count(traffic: dict, seconds: float) -> int:
    """Requests due inside an open loop's window."""
    return max(int(round(traffic["rate_per_s"] * seconds)), 1)


def open_loop_count(traffic: dict, seconds: float) -> int:
    """Requests generated for an open loop: the window's and as many again
    to keep the load on while the window's last requests finish."""
    return 2 * window_count(traffic, seconds) + 16
