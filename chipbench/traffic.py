"""The one traffic generator: turns a mix's parameter file
(``chipbench/traffic/<mix>.json``) and a cell's load settings into a
seeded list of requests.

Lengths are *stratified*: a group of ``n`` requests takes the ``n``
quantiles ``(i + 0.5) / n`` of each length distribution, so every seed
serves the same set of lengths.  Poisson arrivals are stratified the
same way (the gaps are the exponential quantiles).  The seed draws the
order of the gaps and of the lengths, and the token ids: each seed
samples the arrival process anew from the same set of sizes and gaps.
A backlog is all due at once, in waves of the cell's slots, each wave
stratified on its own; the seed shuffles its lengths.

Mix file keys:

- ``arrivals``: ``"poisson"`` (open loop at the cell's ``rate_per_s``
  through its ``warmup_s`` and the window) or ``"backlog"`` (the cell's
  ``requests`` all due at once, before the window, stratified in
  waves of ``block``, the cell's slots).
- ``prompt_tokens`` / ``output_tokens``: ``{"dist": "uniform", "min",
  "max"}`` or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``.
- ``sampling``: ``"greedy"`` (the only mode; the correctness check
  compares greedy tokens).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class RequestRecord:
    """One request as the load generator sees it (host clock, seconds)."""

    rid: int
    prompt: np.ndarray
    gen: int
    offset: float  # due time relative to the start of the schedule
    due: float = float("nan")     # absolute host time it was due
    added: Optional[float] = None  # host time it was handed to add()
    admitted: Optional[float] = None  # host time of the admitting step
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        v = lo + u * (hi - lo + 1)
        return np.clip(np.floor(v), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        return np.clip(np.rint(v), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` quantiles of a length distribution, shuffled."""
    return _quantiles(spec, n)[rng.permutation(n)]


def _poisson_group(n: int, rate: float, start: float,
                   rng: np.random.Generator) -> np.ndarray:
    """Offsets of ``n`` arrivals after ``start``: the ``n`` exponential
    quantiles of the gap at ``rate``, shuffled."""
    u = (np.arange(n) + 0.5) / n
    return start + np.cumsum((-np.log1p(-u) / rate)[rng.permutation(n)])


def generate(mix: dict, *, seed: int, vocab: int, seconds: float,
             warmup: float = 0.0, rate_per_s: Optional[float] = None,
             requests: Optional[int] = None,
             block: Optional[int] = None) -> List[RequestRecord]:
    """The requests of one run.  ``vocab`` bounds the token ids (a sliced
    vocabulary draws from its slice).  Poisson arrivals come in two groups
    stratified apart, those of the ``warmup`` seconds before the window
    and those of its ``seconds``, so the window holds the same requests'
    lengths whatever the warm-up."""
    if mix.get("sampling", "greedy") != "greedy":
        raise ValueError("only greedy sampling is supported")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7ea]))
    kind = mix["arrivals"]
    if kind == "poisson":
        if not rate_per_s or rate_per_s <= 0:
            raise ValueError("poisson arrivals need a positive rate_per_s")
        groups = [int(round(rate_per_s * warmup)),
                  max(1, int(round(rate_per_s * seconds)))]
        offsets = np.concatenate([
            _poisson_group(groups[0], rate_per_s, 0.0, rng),
            _poisson_group(groups[1], rate_per_s, warmup, rng)])
    elif kind == "backlog":
        if not requests:
            raise ValueError("backlog arrivals need a request count")
        n = int(requests)
        groups = [block or n] * (-(-n // (block or n)))
        groups[-1] = n - sum(groups[:-1])
        offsets = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    plen = np.concatenate([_stratified(mix["prompt_tokens"], g, rng)
                           for g in groups if g])
    glen = np.concatenate([_stratified(mix["output_tokens"], g, rng)
                           for g in groups if g])
    out = []
    for i in range(len(offsets)):
        prompt = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int64)
        out.append(RequestRecord(rid=i, prompt=prompt.astype(np.int32),
                                 gen=int(glen[i]), offset=float(offsets[i])))
    return out


def max_tokens(mix: dict) -> int:
    """Longest prompt + output the mix can send (sizes ``max_seq``)."""
    return int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
