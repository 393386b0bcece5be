"""The one general traffic generator. A traffic mix is a data file under
`benchmark/traffic/`; this module turns (file, seed, seconds) into requests.

Every seed gets the SAME multiset of (gap, prompt length, output length)
triples — drawn once from the file's `base_seed` — in another order: the
seed rotates the sequence and draws the token ids. Seeds therefore change
the order and the content of the work, not its amount, so two runs differ
by the system's noise and not by the luck of the draw.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float | None  # seconds from the window's start; None: closed loop
    prompt: list[int]
    max_tokens: int
    measured: bool  # False: pre-roll, sent before the window, not counted


def _strata(n: int, rng: np.random.Generator) -> np.ndarray:
    """n probabilities, one from the middle of each of n equal strata, in
    random order: a sample that carries its distribution exactly, so that
    no run is lucky in the sizes it drew."""
    return (rng.permutation(n) + 0.5) / n


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths from {"dist": "lognormal"|"uniform", ...},
    clipped to [min, max]; stratified (see `_strata`)."""
    dist = spec["dist"]
    u = _strata(n, rng)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif dist == "uniform":
        x = spec["min"] + np.floor(u * (spec["max"] - spec["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", int(np.max(x)))
    return np.clip(np.rint(x), lo, hi).astype(int)


def _rotation(seed: int, n: int) -> int:
    return int(np.random.default_rng(seed).integers(0, n))


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return rng.integers(1, vocab, size=n).tolist()


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> list[Request]:
    """Poisson arrivals at `rate_rps`, scaled so that exactly
    round(rate * seconds) requests fall due inside [0, seconds). The
    requests that precede the window in the rotated (cyclic) order are
    sent during `preroll_s` before it, so the window opens on the queue a
    long-running server would have, whatever the rotation."""
    n = max(2, round(traffic["rate_rps"] * seconds))
    base = np.random.default_rng(traffic["base_seed"])
    gaps = -np.log(1.0 - _strata(n, base))  # exponential, stratified
    gaps *= seconds / gaps.sum()
    plens = draw_lengths(traffic["prompt_len"], n, base)
    olens = draw_lengths(traffic["output_len"], n, base)
    k = _rotation(seed, n)
    gaps, plens, olens = (np.roll(a, -k) for a in (gaps, plens, olens))
    rng = np.random.default_rng(seed)
    # request i is due at the sum of the gaps before it
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    # pre-roll: walk backwards from the end of the cycle
    t, i = 0.0, n - 1
    pre = []
    while i >= 0:
        t -= gaps[i]
        if -t > traffic.get("preroll_s", 0.0):
            break
        pre.append(Request(t, _tokens(rng, plens[i], vocab),
                           int(olens[i]), False))
        i -= 1
    out.extend(reversed(pre))
    for i in range(n):
        out.append(Request(float(due[i]), _tokens(rng, plens[i], vocab),
                           int(olens[i]), True))
    return out


class ClosedPool:
    """The closed loop's requests: sizes cycle through `cycle_requests`
    stratified (prompt, output) pairs, rotated by the seed; every request's
    token ids are its own, so nothing is ever shared with an earlier one."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        n = traffic["cycle_requests"]
        base = np.random.default_rng(traffic["base_seed"])
        k = _rotation(seed, n)
        self.plens = np.roll(draw_lengths(traffic["prompt_len"], n, base), -k)
        self.olens = np.roll(draw_lengths(traffic["output_len"], n, base), -k)
        self.seed, self.vocab, self.n = seed, vocab, n

    def get(self, i: int) -> Request:
        rng = np.random.default_rng([self.seed, i])
        j = i % self.n
        return Request(None, _tokens(rng, self.plens[j], self.vocab),
                       int(self.olens[j]), True)
