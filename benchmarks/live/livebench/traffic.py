"""The one traffic generator: a mix file's parameters and a seed in, a
schedule out.

A mix (``traffic/<name>.json``) says whether a malleable trainer runs and
how it is stepped, whether an on-demand job arrives (when, on how many
nodes, for how long), and the request stream that job serves: an open
loop at ``rate_per_s``, prompt lengths from the ranges ``prompt_lens``
([shortest, longest] each) by ``weights``, and ``new_tokens`` greedy
tokens each.

The stream is made of blocks of ``block`` requests.  Every block holds
the same work: its inter-arrival gaps are the exponential distribution's
quantiles at the mix's rate, and its prompts fall in the ranges in the
shares of the weights.  The seed orders each block's gaps and lengths,
picks each prompt's length within its range, and draws the tokens.  So
every seed offers the same load at every moment, in another order: with
one order drawn over the whole stream, the p95 time to first token of
one chip's serving cell spread by 13% between seeds against a few
percent between runs of one seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

@dataclass
class Req:
    """One request of the stream: due ``due_s`` after the stream opens."""

    rid: int
    due_s: float
    prompt: np.ndarray
    new_tokens: int


@dataclass
class Schedule:
    trainer: Optional[str]          # None | "events" | "step_all"
    od_nodes: int                   # 0: no on-demand job
    od_due_s: Optional[float]       # None: the job starts during set-up
    od_hold_s: Optional[float]      # None: it holds to the window's end
    stream_s: float                 # how long requests keep arriving
    requests: List[Req]
    max_batch: int


def stream_length(mix: dict, seconds: float) -> float:
    """Seconds over which requests arrive, counted from the on-demand
    job's due time (or the window's start)."""
    od, rq = mix.get("ondemand"), mix.get("requests")
    if not od or not rq:
        return 0.0
    start = od["due_s"] or 0.0
    end = seconds if od["hold_s"] is None else min(seconds,
                                                   start + od["hold_s"])
    return max(0.0, end - start - rq["tail_s"])


def _counts(weights, n: int) -> np.ndarray:
    """``n`` split by ``weights``, largest remainders rounded up."""
    w = np.asarray(weights, float)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def make(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    od, rq = mix.get("ondemand"), mix.get("requests")
    stream_s = stream_length(mix, seconds)
    reqs: List[Req] = []
    if rq and stream_s > 0:
        rng = np.random.default_rng(seed)
        rate, m = rq["rate_per_s"], rq["block"]
        n = max(1, int(round(rate * stream_s)))
        gap0 = -np.log1p(-(np.arange(m) + 0.5) / m)
        gap0 *= m / rate / gap0.sum()           # a mean gap of 1 / rate
        ranges = np.repeat(np.asarray(rq["prompt_lens"]),
                           _counts(rq["weights"], m), axis=0)
        n_blocks = -(-n // m)
        gaps = np.concatenate([rng.permutation(gap0)
                               for _ in range(n_blocks)])[:n]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        lr = np.concatenate([rng.permutation(ranges)
                             for _ in range(n_blocks)])[:n]
        lens = rng.integers(lr[:, 0], lr[:, 1] + 1)
        for i in range(n):
            reqs.append(Req(rid=i, due_s=float(due[i]),
                            prompt=rng.integers(0, vocab, int(lens[i]),
                                                dtype=np.int32),
                            new_tokens=int(rq["new_tokens"])))
    return Schedule(trainer=mix.get("trainer"),
                    od_nodes=od["nodes"] if od else 0,
                    od_due_s=od["due_s"] if od else None,
                    od_hold_s=od["hold_s"] if od else None,
                    stream_s=stream_s, requests=reqs,
                    max_batch=rq["max_batch"] if rq else 0)


def shapes(mix: dict, padded_len) -> List[tuple]:
    """Every (batch, padded prompt length) a mix's batches can take, where
    ``padded_len`` is the serving engine's rule for a batch's longest
    prompt."""
    rq = mix.get("requests")
    if not rq:
        return []
    lens = sorted({padded_len(n) for lo, hi in rq["prompt_lens"]
                   for n in range(lo, hi + 1)})
    return [(b, s) for s in lens for b in range(1, rq["max_batch"] + 1)]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q))
