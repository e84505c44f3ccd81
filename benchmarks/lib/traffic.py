"""Seeded generators: one token corpus, one request trace.

Every traffic mix is a data file of parameters read by these two
functions; a new mix needs no code."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def token_corpus(seed: int, rows: int, seq: int, vocab: int,
                 zipf_exponent: float = 1.0) -> np.ndarray:
    """``(rows, seq + 1)`` int32 tokens from one fixed Zipf unigram
    distribution, so a loss has somewhere to fall (uniform tokens start at
    the floor, ln V).  Column 0..seq-1 is the input, 1..seq the target."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_exponent
    p /= p.sum()
    rng = np.random.default_rng([int(seed), 1])
    return rng.choice(vocab, size=(rows, seq + 1), p=p).astype(np.int32)


def _lognormal_quantiles(n: int, median: float, sigma: float,
                         lo: int, hi: int) -> np.ndarray:
    """The n mid-quantiles of a log-normal, clipped: the same multiset of
    sizes for every seed."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def request_trace(seed: int, mix: dict, seconds: float, vocab: int,
                  max_len: int) -> list[dict]:
    """An open-loop arrival trace over ``seconds``.

    ``round(rate * seconds)`` requests.  Prompt lengths, new-token counts
    and inter-arrival gaps are each the mid-quantiles of their
    distribution (log-normal, log-normal, exponential), so every seed
    offers the same multiset of work.  The order of each of the three is
    the mix's own, a permutation drawn once from its ``order_seed``; the
    run's seed draws the token ids and nothing else.  (Measured with the
    seed choosing the order: two seeds differed by 11% in tokens/s and
    16% in the 90th-percentile TTFT, because a queue's tails and the
    window's end follow the order of bursts and long requests; no bound
    that may be set would hold.  PERF.md says by what rule a mix's
    ``order_seed`` is chosen.)  Returns dicts ``rid, arrival_s, prompt,
    max_new_tokens`` sorted by arrival."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    rng = np.random.default_rng([int(seed), 2])
    pl = _lognormal_quantiles(n, **mix["prompt_tokens"])
    nl = _lognormal_quantiles(n, **mix["new_tokens"])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)   # exponential quantiles
    gaps = gaps / gaps.sum() * seconds        # the n arrivals span the window
    order = np.random.default_rng([int(mix.get("order_seed", 0)), 5])
    pl, nl, gaps = (order.permutation(a) for a in (pl, nl, gaps))
    arrivals = np.cumsum(gaps) - gaps[0]      # the first request is due at 0
    out = []
    for i in range(n):
        lp = int(min(pl[i], max_len - 1 - 1))
        new = int(min(nl[i], max_len - lp))
        out.append({"rid": i, "arrival_s": float(arrivals[i]),
                    "prompt": rng.integers(0, vocab, lp, dtype=np.int32),
                    "max_new_tokens": new})
    return out
