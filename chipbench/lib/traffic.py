"""The one traffic generator: reads a mix file's parameters and makes the
run's requests from ``--seed``.

Every seed gets the same multiset of sizes and gaps: prompt and output
lengths, the greedy/sampled split and the inter-arrival gaps are fixed
quantiles of the mix's distributions (in each block, see ``block``), and
the seed only shuffles them and draws the prompt tokens.  So two seeds do the same work in another order,
and a difference between seeds is not a difference of load.

A mix file (``chipbench/traffic/<name>.json``) holds:

* ``kind``: ``"serve"``;
* ``loop``: ``"closed"`` (``clients_per_slot`` × ``num_slots`` clients, each
  sending its next request when its last one finishes) or ``"poisson"``
  (open loop at ``rate_per_s``, after ``preroll_s`` seconds of the same
  arrivals before the window opens);
* ``requests``: how many requests the pool holds;
* optionally ``block``: the pool is made of blocks of this many requests
  (a whole number of them), each with the same sizes, sampled share and
  inter-arrival gaps in its own order, so that every ``block`` arrivals
  carry the same work over the same time; without it, one block;
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}`` in tokens;
* ``sampled``: ``{"every": n, "temperature", "top_p"}`` — one request in
  ``n`` samples, the rest are greedy;
* optionally ``start``: ``"mid-request"`` — a closed loop starts as one that
  has run for a while, each slot's first request part-way through its
  output (``mid_request``);
* optionally ``bursts``: ``{"on_s", "off_s"}`` — an open loop's arrivals
  come only in ``on_s``-second spells, ``off_s`` seconds apart.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: tuple
    max_new: int
    temperature: float
    top_p: float
    due: float          # seconds after the first arrival (open loop), else 0


def sizes(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + ½)/n of the distribution,
    clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in q])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """n exponential inter-arrival gaps at the mid-quantiles, scaled to
    the mean 1/rate: the offered rate is the mix's own, however few gaps a
    block holds (the mid-quantiles alone fall short of the tail)."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g / (g.mean() * rate)


def generate(mix: dict, seed: int, vocab: int) -> list:
    if mix.get("kind") != "serve":
        raise ValueError(f"not a serving mix: kind {mix.get('kind')!r}")
    n = int(mix["requests"])
    block = int(mix.get("block", n))
    if n % block:
        raise ValueError(f"requests {n} is not a whole number of blocks "
                         f"of {block}")
    rng = np.random.default_rng(int(seed))

    def shuffled(values):
        """The same values in every block, each block in its own order."""
        return np.concatenate([rng.permutation(values)
                               for _ in range(n // block)])

    plen = shuffled(sizes(mix["prompt"], block))
    olen = shuffled(sizes(mix["output"], block))
    s = mix["sampled"]
    sampled = shuffled(np.arange(block) % int(s["every"]) == 0)
    if mix["loop"] == "poisson":
        rate = float(mix["rate_per_s"])
        gap = (shuffled(gaps(rate, block))[:n - 1] if "block" in mix
               else rng.permutation(gaps(rate, n - 1)))
        due = np.concatenate([[0.0], np.cumsum(gap)])
        if "bursts" in mix:
            # arrivals in "on" time, each spell followed by an off spell
            on, off = (float(mix["bursts"][k]) for k in ("on_s", "off_s"))
            due = due + np.floor(due / on) * off
    elif mix["loop"] == "closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    out = []
    for i in range(n):
        out.append(Request(
            prompt=tuple(int(t) for t in rng.integers(0, vocab, plen[i])),
            max_new=int(olen[i]),
            temperature=float(s["temperature"]) if sampled[i] else 0.0,
            top_p=float(s["top_p"]) if sampled[i] else 1.0,
            due=float(due[i])))
    return out


def mid_request(reqs: list, n: int, seed: int, vocab: int) -> list:
    """The first ``n`` requests as a closed loop that has run for a while
    holds them: request k has served the fraction f_k of its output, the
    f_k being the mid-quantiles (j + ½)/n in an order drawn from the seed.
    Its served tokens (drawn from the seed) join its prompt and its budget
    shrinks by as many, so the window's contexts span whole requests and
    its slots come free at staggered times."""
    rng = np.random.default_rng([int(seed), 1])
    frac = rng.permutation((np.arange(n) + 0.5) / n)
    out = list(reqs)
    for k, r in enumerate(reqs[:n]):
        done = int(frac[k] * r.max_new)
        served = tuple(int(t) for t in rng.integers(0, vocab, done))
        out[k] = dataclasses.replace(r, prompt=r.prompt + served,
                                     max_new=r.max_new - done)
    return out
