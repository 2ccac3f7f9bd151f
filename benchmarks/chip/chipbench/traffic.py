"""The one traffic generator every mix file feeds.

A mix (``traffic/<name>.json``) gives the loop kind, its rate or client
count, and the length distributions.  From the mix's own ``base_seed`` the
generator draws one fixed pool of requests (prompt and answer lengths, and
for an open loop the gaps between arrivals); a run's ``--seed`` only
permutes that pool and draws the prompt tokens.  So every seed offers the
same work in another order, and two runs of one seed offer the same inputs.

An open loop's pool is drawn per phase (pre-roll, window, drain): each
phase holds ``round(rate * length)`` arrivals, spaced as a Poisson process
given that count, and a seed permutes sizes and spacings within a phase
only.  So the window offers the same requests under every seed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the pool."""

    uid: int
    prompt_len: int
    max_new: int
    offset: float = 0.0    # open loop: due time relative to window start


def seed_words(seed: int) -> List[int]:
    """A run seed (any non-negative whole number, past 32 bits too) as
    numpy seed words."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64]


def draw_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        x = np.rint(x)
    elif spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def phases(mix: dict, seconds: float) -> List[tuple]:
    """(start, length) of an open loop's phases, relative to the window."""
    pre, drain = float(mix["preroll_s"]), float(mix["drain_limit_s"])
    return [(-pre, pre), (0.0, float(seconds)), (float(seconds), drain)]


def pool_size(mix: dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return int(mix["pool_size"])
    return sum(_arrivals(mix, length) for _, length in phases(mix, seconds))


def _arrivals(mix: dict, length: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * length))) if length > 0 else 0


def build_pool(mix: dict, seconds: float, seed: int) -> List[Planned]:
    """The run's requests in the order they are offered."""
    base = np.random.default_rng(int(mix["base_seed"]))
    run = np.random.default_rng(seed_words(seed))
    if mix["loop"] == "closed":
        n = pool_size(mix, seconds)
        prompts = draw_lengths(mix["prompt_len"], base, n)
        outputs = draw_lengths(mix["output_len"], base, n)
        order = run.permutation(n)
        return [Planned(uid=i, prompt_len=int(prompts[j]),
                        max_new=int(outputs[j]))
                for i, j in enumerate(order)]
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    pool: List[Planned] = []
    for start, length in phases(mix, seconds):
        n = _arrivals(mix, length)
        if n == 0:
            continue
        prompts = draw_lengths(mix["prompt_len"], base, n)
        outputs = draw_lengths(mix["output_len"], base, n)
        spacing = base.exponential(1.0, n + 1)
        order, gaps = run.permutation(n), run.permutation(spacing)
        due = start + length * np.cumsum(gaps)[:n] / gaps.sum()
        for j, t in zip(order, due):
            pool.append(Planned(uid=len(pool), prompt_len=int(prompts[j]),
                                max_new=int(outputs[j]), offset=float(t)))
    return pool


def make_prompt(seed: int, uid: int, length: int, vocab: int) -> np.ndarray:
    """Per-request prompt tokens, replayable from (seed, uid)."""
    rng = np.random.default_rng(seed_words(seed) + [uid])
    return rng.integers(0, vocab, length, dtype=np.int32)
