"""One general generator for every traffic mix.

A mix file fixes a multiset of prompt lengths, output lengths and, for an
open loop, inter-arrival gaps, each drawn once from fixed quantiles of the
stated distribution.  ``--seed`` only permutes their order and draws the
token ids, so every seed offers the same load.

Distributions (``dist``):

* ``lognormal``: ``median`` and ``sigma`` of the log;
* ``power``: density rising as ``x**(k-1)`` from ``min`` to ``max``,
  weighted toward ``max`` for ``k > 1``;
* ``uniform``: from ``min`` to ``max``.

Every draw is clipped to ``[min, max]`` and rounded.  A prompt is cut so
that prompt plus output fits ``max_total``.  Prompt lengths are made
distinct (each duplicate moves to the nearest free length), as real
prompts of hundreds of tokens rarely share an exact length; the engine
batches an admission wave only over equal lengths, so every wave is a
single request and its shapes are known before the window.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def quantiles(n: int) -> np.ndarray:
    """The n mid-point quantile levels (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def draw(dist: dict, n: int) -> np.ndarray:
    """n values at fixed quantiles of ``dist`` (float, unclipped)."""
    u = quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        return np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    if kind == "power":
        lo, hi, k = dist["min"], dist["max"], dist["k"]
        return lo + (hi - lo) * u ** (1.0 / k)
    if kind == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * u
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(dist: dict, n: int) -> np.ndarray:
    v = np.rint(draw(dist, n))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def make_distinct(vals: np.ndarray, lo: int, his: np.ndarray) -> np.ndarray:
    """Move duplicates to the nearest free value within [lo, his[i]]."""
    out = vals.copy()
    used = set()
    for i in np.argsort(vals, kind="stable"):
        v = int(vals[i])
        for d in range(0, 1 << 20):
            for c in ((v + d, v - d) if d else (v,)):
                if lo <= c <= his[i] and c not in used:
                    out[i] = c
                    used.add(c)
                    break
            else:
                continue
            break
        else:
            raise ValueError("no free prompt length left")
    return out


def gaps(rate: float, n: int) -> np.ndarray:
    """Poisson-shaped inter-arrival gaps: n fixed quantiles of the
    exponential law of mean 1/rate."""
    return -np.log1p(-quantiles(n)) / rate


@dataclasses.dataclass(frozen=True)
class Mix:
    """The seed-free multiset (pairs fixed by ``pairing_seed``)."""
    prompt_lens: np.ndarray    # (n,)
    out_lens: np.ndarray       # (n,)
    gaps: np.ndarray           # (n,) seconds; empty for a closed loop


def multiset(traffic: dict, n: int, rate: float | None = None) -> Mix:
    out = lengths(traffic["output"], n)
    prompt = lengths(traffic["prompt"], n)
    # fixed pairing of prompts with outputs, the same for every seed
    prompt = prompt[np.random.default_rng(
        traffic["pairing_seed"]).permutation(n)]
    his = np.minimum(traffic["prompt"]["max"], traffic["max_total"] - out)
    prompt = np.minimum(prompt, his)
    prompt = make_distinct(prompt, traffic["prompt"]["min"], his)
    g = gaps(rate, n) if traffic["loop"] == "open" else np.zeros(0)
    return Mix(prompt, out, g)


def request_count(traffic: dict, seconds: float, rate: float | None) -> int:
    """Requests in a schedule: for an open loop every request that can be
    due before the loop ends (ramp, window and grace, with a tenth to
    spare: the fixed quantile gaps sum to a little under n / rate); for a
    closed loop the mix's pool, which the clients cycle."""
    if traffic["loop"] == "open":
        span = traffic["ramp_s"] + seconds + traffic["grace_s"]
        return int(math.ceil(rate * span * 1.1)) + 2
    return int(traffic["requests"])


@dataclasses.dataclass(frozen=True)
class Schedule:
    prompts: list              # token id arrays, in submission order
    out_lens: np.ndarray
    due: np.ndarray            # open loop: due time after the loop starts


def seed_rng(seed: int) -> np.random.Generator:
    """Any whole number, negative or past 64 bits, maps to a generator."""
    return np.random.default_rng(int(seed) % (1 << 64))


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> Schedule:
    """The mix's multiset of ``traffic["requests"]`` in the seed's order,
    repeated as often as the run needs (a repeat is the same requests,
    one whole multiset later, so equal prompt lengths never wait at the
    same time)."""
    n = int(traffic["requests"])
    mix = multiset(traffic, n, rate)
    rng = seed_rng(seed)
    order = rng.permutation(n)
    gap_order = rng.permutation(n)
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32)
               for p in mix.prompt_lens[order]]
    total = max(n, request_count(traffic, seconds, rate))
    reps = -(-total // n)
    due = np.zeros(n * reps)
    if traffic["loop"] == "open":
        due = np.cumsum(np.tile(mix.gaps[gap_order], reps))
    return Schedule((prompts * reps)[:total],
                    np.tile(mix.out_lens[order], reps)[:total], due[:total])
