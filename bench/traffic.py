"""Traffic from a mix file (``traffic/<mix>.json``) and a seed.

Lengths are clipped lognormal and gaps between arrivals exponential, as
in ``repro.serving.traffic.generate_trace``; but every seed gets the
same multiset of them, in its own order.  The lengths are the
distribution's quantiles at ``(i + 1/2) / n``, and the gaps the
exponential's quantiles, scaled so that the ``n = rate * seconds``
arrivals span the window exactly.  So the seed changes which request
comes when, the pairing of prompt and answer lengths, and the tokens,
but never how much work a window holds.  (Gaps are exchangeable rather
than independent, so the arrivals are Poisson-like, not Poisson.)

Mix file keys: ``loop`` ("open" | "closed"), ``rate_per_s`` (open),
``concurrency`` and ``pool`` (closed), ``prompt`` and ``output``
(``mean``, ``sigma``, ``min``, ``max`` in tokens), ``check`` (requests
the correctness check samples: ``requests``, ``min_tokens``).  A
``"static"`` mix (``bench/batch.py``) holds ``batch``, ``prompt`` and
``output`` as whole numbers, and a ``check`` with ``requests`` and its
limit, ``mean_logit_gap``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due: float                  # seconds after the window opens (open
                                # loop); 0 in a closed loop's pool
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def quantile_lengths(n: int, p: dict) -> np.ndarray:
    """``n`` clipped-lognormal lengths with mean ``p["mean"]`` before
    clipping, at the quantiles ``(i + 1/2) / n``."""
    sigma = float(p["sigma"])
    mu = math.log(p["mean"]) - 0.5 * sigma ** 2
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(np.exp(mu + sigma * z)), p["min"],
                   p["max"]).astype(np.int64)


def quantile_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` exponential gaps at the quantiles ``(i + 1/2) / n``, scaled
    to sum to ``seconds``."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g * (seconds / g.sum())


def n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return int(mix["pool"])


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list[Request]:
    """The requests of one run.  Open loop: due times inside
    ``[0, seconds)``; ``rate`` overrides the mix's (the sweep).  Closed
    loop: a pool the clients draw from in order."""
    if rate is not None:
        mix = dict(mix, rate_per_s=rate)
    n = n_requests(mix, seconds)
    rng = np.random.default_rng(int(seed))
    p_len = rng.permutation(quantile_lengths(n, mix["prompt"]))
    o_len = rng.permutation(quantile_lengths(n, mix["output"]))
    if mix["loop"] == "open":
        gaps = rng.permutation(quantile_gaps(n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = np.zeros(n)
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(p_len[i])).astype(np.int32),
                    int(o_len[i]))
            for i in range(n)]
