"""One general traffic generator, driven by the parameters of a mix file.

A mix (``bench/traffic/<name>.json``) gives its arrivals and its length
distributions:

* ``arrivals.kind``: ``closed`` (``concurrency`` clients, each sending
  its next request when the last one finished; ``"slots"`` means one
  client per engine slot), ``poisson`` (open loop at ``rate_per_s``) or
  ``bursts`` (open loop: bursts of ``burst_min``..``burst_max`` requests
  spread over ``burst_span_s``, burst starts exponentially apart, for a
  mean of ``rate_per_s`` requests a second);
* ``prompt_tokens`` / ``output_tokens``: lognormal with ``median`` and
  ``sigma``, clipped to ``min``..``max``;
* ``strata``: lengths and gaps are drawn as stratified quantiles: the
  midpoints of ``strata`` equal-probability bins, repeated in blocks in
  a balanced order (low and high strata paired, so any stretch of the
  stream holds close to the mean).

The sizes and the arrival times are the same for every seed; the seed
draws the prompts' token ids (and, in ``bench/harness/weights.py``, the
weights). Runs on different seeds then do the same work, and their
spread is the system's, not the traffic's.

A closed loop starts near steady state: the first ``concurrency``
requests fill the slots during set-up with residual budgets (the
unfinished part of a request already in flight) spread evenly over
(0, median output), so requests finish all through the window instead
of all at once.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_UNIT = NormalDist()


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    offset_s: float | None = None  # open loop: due time after window start


@dataclasses.dataclass
class Traffic:
    closed: bool
    concurrency: int
    initial: list[Request]  # closed loop: fills the slots in set-up
    stream: list[Request]  # closed: next requests in order; open: by due time
    warmup: list[Request]  # open loop: compiles every shape before the window


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *tags])


def _lognormal_strata(d: dict, k: int) -> np.ndarray:
    q = (np.arange(k) + 0.5) / k
    vals = [d["median"] * math.exp(d["sigma"] * _UNIT.inv_cdf(x)) for x in q]
    return np.clip(np.rint(vals), d["min"], d["max"]).astype(np.int64)


def _balanced_order(k: int) -> np.ndarray:
    """Strata 0..k-1 ordered so that any stretch holds close to the mean:
    low and high strata in adjacent pairs (j, k-1-j), the pairs in
    bit-reversed order, alternately low-high and high-low."""
    half = k // 2
    bits = max(half - 1, 1).bit_length()
    rev = sorted(range(half), key=lambda j: int(f"{j:0{bits}b}"[::-1], 2))
    order = []
    for n, j in enumerate(rev):
        order.extend([j, k - 1 - j] if n % 2 == 0 else [k - 1 - j, j])
    return np.asarray(order + ([half] if k % 2 else []))


def _stratified(values: np.ndarray, n: int, reverse: bool) -> np.ndarray:
    """``n`` values: repeated blocks of the sorted strata ``values`` in
    the balanced order (reversed for the second length of a pair, so a
    request's prompt and output strata are not tied)."""
    order = _balanced_order(len(values))
    block = values[order[::-1] if reverse else order]
    return np.resize(block, n)


def _prompt(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    return _rng(seed, 7, index).integers(0, vocab, size=length).astype(np.int32)


def lengths(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    k = int(mix["strata"])
    p = _stratified(_lognormal_strata(mix["prompt_tokens"], k), n, False)
    o = _stratified(_lognormal_strata(mix["output_tokens"], k), n, True)
    return p, o


def _open_offsets(arr: dict, seconds: float, k: int) -> list[float]:
    q = (np.arange(k) + 0.5) / k
    unit_gaps = -np.log1p(-q)  # exponential quantiles, mean ~1
    rate = float(arr["rate_per_s"])
    offsets: list[float] = []
    if arr["kind"] == "poisson":
        t = 0.0
        while True:
            for g in unit_gaps[_balanced_order(k)]:
                t += g / rate
                if t >= seconds:
                    return offsets
                offsets.append(t)
    if arr["kind"] == "bursts":
        # one block: a burst of every size, burst starts at stratified
        # exponential gaps, both in the balanced order
        sizes = np.arange(int(arr["burst_min"]), int(arr["burst_max"]) + 1)
        nb = len(sizes)
        gaps = -np.log1p(-(np.arange(nb) + 0.5) / nb) * sizes.mean() / rate
        span = float(arr["burst_span_s"])
        t = 0.0
        while True:
            for g, size in zip(gaps[_balanced_order(nb)],
                               sizes[_balanced_order(nb)[::-1]]):
                t += g
                if t >= seconds:
                    return sorted(offsets)
                offsets.extend(t + span * j / size for j in range(size)
                               if t + span * j / size < seconds)
    raise ValueError(f"unknown arrivals kind {arr['kind']!r}")


def generate(mix: dict, seed: int, *, vocab: int, n_slots: int,
             seconds: float, max_len: int) -> Traffic:
    """The requests of one run of ``mix`` from ``seed``."""
    arr = mix["arrivals"]
    k = int(mix["strata"])
    hi_p, hi_o = mix["prompt_tokens"]["max"], mix["output_tokens"]["max"]
    if hi_p + hi_o > max_len:
        raise ValueError(f"mix needs {hi_p + hi_o} positions, deployment "
                         f"holds {max_len}")
    if arr["kind"] == "closed":
        conc = n_slots if arr["concurrency"] == "slots" else int(arr["concurrency"])
        # enough for every slot to turn over many times at any speed
        n = conc + 64 * k
        p, o = lengths(mix, n)
        reqs = [Request(i, _prompt(seed, i, int(p[i]), vocab), int(o[i]))
                for i in range(n)]
        # residual budgets evenly spread over (0, median output), so the
        # first requests finish at the same times whatever the seed
        fractions = (np.arange(conc) + 0.5) / conc
        median = mix["output_tokens"]["median"]
        for r, f in zip(reqs[:conc], fractions):
            r.max_new = max(1, math.ceil(median * f))
        return Traffic(True, conc, reqs[:conc], reqs[conc:], [])
    offsets = _open_offsets(arr, seconds, k)
    p, o = lengths(mix, len(offsets))
    stream = [Request(i, _prompt(seed, i, int(p[i]), vocab), int(o[i]), t)
              for i, t in enumerate(offsets)]
    warmup = [Request(-1 - s,
                      _rng(seed, 8, s).integers(0, vocab, 2).astype(np.int32), 2)
              for s in range(n_slots)]
    return Traffic(False, n_slots, [], stream, warmup)
