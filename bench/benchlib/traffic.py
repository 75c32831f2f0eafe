"""The one general traffic generator: a mix file of parameters in, a seeded
open-loop schedule of requests out.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps -- the quantiles of the mix's distributions at
``(i + 0.5) / n`` -- in an order the seed permutes, and its own token ids.
So two seeds offer the same work in a different order, and the spread
between seeds is the system's, not the generator's.

Mix file keys (``traffic/<mix>.json``):

    arrivals  {"kind": "poisson"} or {"kind": "gamma", "cv": c}: the
              distribution of gaps between due times at the cell's rate
              (gamma with cv > 1 is bursty: the ``chat-bursty`` mix to come)
    prompt    a length distribution: {"dist": "lognormal", "median", "sigma",
              "min", "max"}
    output    the same for ``max_tokens``; decoding runs to it (no EOS)
    shared_prefix  optional {"docs", "length", "zipf_s"}: each prompt starts
              with one of ``docs`` seeded documents, drawn by a Zipf law,
              and every document is sent once during set-up (the ``docqa``
              mix to come)
    greedy    true: temperature 0
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    t_due: float              # seconds after the window opens
    prompt: List[int]
    max_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a length distribution, ascending, as ints."""
    kind = dist["dist"]
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def gaps(arrivals: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps whose sum is ``seconds`` (ascending)."""
    q = _quantiles(n)
    kind = arrivals["kind"]
    if kind == "poisson":
        g = -np.log1p(-q)
    elif kind == "gamma":
        shape = 1.0 / arrivals["cv"] ** 2
        rng = np.random.default_rng(0)       # a fixed sample, sorted
        g = np.sort(rng.gamma(shape, 1.0 / shape, size=n))
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return g * (seconds / g.sum())


def documents(mix: dict, seed: int, vocab: int) -> List[List[int]]:
    """The shared-prefix documents of a mix (none without one)."""
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, sp["length"]).tolist()
            for _ in range(sp["docs"])]


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Request]:
    """The open-loop schedule of one run: ``round(rate * seconds)`` requests
    due inside ``[0, seconds)``, the first at 0."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 0])
    plen = rng.permutation(lengths(mix["prompt"], n))
    olen = rng.permutation(lengths(mix["output"], n))
    g = rng.permutation(gaps(mix["arrivals"], n, seconds))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    docs = documents(mix, seed, vocab)
    if docs:
        w = 1.0 / np.arange(1, len(docs) + 1) ** mix["shared_prefix"]["zipf_s"]
        pick = rng.choice(len(docs), size=n, p=w / w.sum())
    out = []
    for i in range(n):
        prefix = docs[pick[i]] if docs else []
        tail = rng.integers(0, vocab, int(plen[i])).tolist()
        out.append(Request(index=i, t_due=float(due[i]),
                           prompt=prefix + tail, max_tokens=int(olen[i])))
    return out


def warmup_requests(seed: int, vocab: int, prompt_len: int = 200,
                    max_tokens: int = 8, count: int = 2) -> List[Request]:
    """A few short requests that walk the HTTP path before the window."""
    rng = np.random.default_rng([seed, 2])
    return [Request(index=-1 - i, t_due=0.0,
                    prompt=rng.integers(0, vocab, prompt_len).tolist(),
                    max_tokens=max_tokens) for i in range(count)]
