"""The one traffic generator: reads a mix's parameters and makes its
requests from the seed.

A mix (``bench/traffic/<name>.json``) gives the arrivals, the prompt
length, the output-length distribution and the pod that serves it:

    {"arrivals": {"kind": "poisson", "rate_per_s": 1.6}
              or {"kind": "bursty", "base_per_s": 3.0, "burst_factor": 4,
                  "period_s": 10, "burst_s": 2, "first_burst_s": 4}
              or {"kind": "backlog"},
     "prompt_len": 256,
     "output_len": {"kind": "lognormal", "median": 96, "sigma": 0.5,
                    "min": 16, "max": 256},
     "pod": {"batch": 16, "max_seq": 512, "quota": 1.0}}

Every seed gets the same work, so that a seed changes the inputs, not
how much there is to do or which requests meet in a batch:

- ``poisson``: ``round(rate * seconds)`` requests due inside the window.
  Their gaps are the exponential distribution's quantiles at
  ``(i + 0.5) / n`` and their output lengths the length distribution's,
  each shuffled once by a fixed ``BASE_ORDER``: one schedule of (due
  time, length) for every seed, which then draws only the prompts and
  the weights. On a v5e, chat latency spread less across six seeds on
  this one schedule (p50 3-9 %, p95 8-14 %) than with an order drawn from
  each seed (p95 12-17 %). What spread remains is run-to-run timing: in
  a static-batching queue a few milliseconds decide which batch a
  request waits behind.
- ``bursty``: the window on a fixed timeline of stretches. Bursts run
  over ``[first_burst_s + k * period_s, ... + burst_s)`` at
  ``burst_factor * base_per_s``; the stretches between them at
  ``base_per_s``. Each stretch holds ``round(rate * length)`` requests,
  spaced as ``poisson`` spaces the whole window (quantile gaps shuffled
  by ``BASE_ORDER``, scaled to the stretch); the output lengths are the
  distribution's quantiles over all of the window's requests, shuffled
  once. One schedule for every seed, as for ``poisson``.
- ``backlog``: a queue that never empties. Requests come in groups of the
  pod's batch; each group holds the length distribution's ``batch``
  quantiles, in an order drawn from the seed. The batcher serves a full
  queue first in, first out, so every batch holds the same lengths.

Prompt token ids are uniform over ``[1, vocab)``, drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional, Tuple

import numpy as np

BASE_ORDER = 20250501  # the one shuffle of an open-loop mix's quantiles


@dataclasses.dataclass
class Planned:
    """One request to send. ``due`` is seconds after the window opens;
    None for a backlog request, which is due when it is queued."""
    due: Optional[float]
    prompt: np.ndarray
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles at ``(i + 0.5) / n`` of the output-length
    distribution, rounded and clipped to ``[min, max]``, ascending."""
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown output_len kind {spec['kind']!r}")
    z = NormalDist()
    q = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(int)


def _prompts(rng, n: int, length: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, (n, length), dtype=np.int32)


def stretches(arrivals: dict, seconds: float,
              rate_per_s: Optional[float] = None
              ) -> List[Tuple[float, float, int]]:
    """The window's stretches of one rate, as (start, end, requests), in
    order. ``rate_per_s`` overrides the mix's rate (``poisson``) or base
    rate (``bursty``)."""
    kind = arrivals["kind"]
    if kind == "poisson":
        rate = rate_per_s or arrivals["rate_per_s"]
        return [(0.0, seconds, max(1, round(rate * seconds)))]
    if kind != "bursty":
        raise ValueError(f"not an open-loop mix: {kind!r}")
    base = rate_per_s or arrivals["base_per_s"]
    burst = arrivals["burst_factor"] * base
    edges, t = [], float(arrivals["first_burst_s"])
    while t < seconds:
        edges.append((t, min(t + arrivals["burst_s"], seconds)))
        t += arrivals["period_s"]
    out, prev = [], 0.0
    for a, b in edges:
        out += [(prev, a, base), (a, b, burst)]
        prev = b
    out.append((prev, seconds, base))
    return [(a, b, round(r * (b - a))) for a, b, r in out if b > a]


def schedule(traffic: dict, seconds: float,
             rate_per_s: Optional[float] = None) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """Due times (seconds after the window opens, ascending) and output
    lengths of an open-loop mix: the same for every seed."""
    order = np.random.default_rng(BASE_ORDER)
    dues = []
    for a, b, n in stretches(traffic["arrivals"], seconds, rate_per_s):
        if n == 0:
            continue
        gaps = order.permutation([-math.log(1.0 - (i + 0.5) / n)
                                  for i in range(n)])
        gaps *= (b - a) / gaps.sum()
        dues.append(a + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
    due = np.concatenate(dues)
    lens = order.permutation(output_lengths(traffic["output_len"],
                                            len(due)))
    return due, lens


def open_loop(traffic: dict, seconds: float, seed: int, vocab: int,
              rate_per_s: Optional[float] = None) -> List[Planned]:
    """The requests due inside a window of ``seconds``, in due order.
    ``rate_per_s`` overrides the mix's rate (for a sweep)."""
    due, lens = schedule(traffic, seconds, rate_per_s)
    prompts = _prompts(_rng(seed, 2), len(due), traffic["prompt_len"], vocab)
    return [Planned(float(d), p, int(m))
            for d, p, m in zip(due, prompts, lens)]


def backlog(traffic: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """An endless queue, one pod batch of lengths at a time."""
    if traffic["arrivals"]["kind"] != "backlog":
        raise ValueError("not a backlog mix")
    B = traffic["pod"]["batch"]
    lens = output_lengths(traffic["output_len"], B)
    order, tok = _rng(seed, 1), _rng(seed, 2)
    while True:
        prompts = _prompts(tok, B, traffic["prompt_len"], vocab)
        for p, m in zip(prompts, order.permutation(lens)):
            yield Planned(None, p, int(m))
