"""One general generator for every traffic mix (``traffic/<mix>.json``).

A mix is data: an arrival process (Poisson) at a rate, and the
distributions of prompt and output lengths. Every request of a run is
fixed by the mix's own ``shape_seed``: its prompt and output lengths and
its prompt's token ids. So is the arrival process of each part of the run
(the lead-in, the measured window, the drain after it): how many requests
arrive in it, and the multiset of gaps between them, which sum to the
part's length. The run seed only orders each part: which request comes
when, and which gap follows which. Every seed therefore serves the same
work: in a cascade, which requests escalate is a property of the request
and the weights, and a seed that drew other requests would escalate
others and change the work (the TTFT tail rests on the long escalated
requests).

The gear's calibration probe draws its requests from the same
``shape_seed``, under a stream the measured requests never use, so the
threshold is fixed too.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Arrival:
    rid: int
    due: float             # seconds after the run's clock starts
    prompt: np.ndarray     # (L,) int32
    max_new: int
    key: int = -1          # the request's identity, the same in every seed


def draw_lengths(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def parts(mix: dict, seconds: float) -> List[tuple]:
    """(start, length, number of arrivals) of the lead-in, the window and
    the drain: the rate times the length, rounded."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    lead, drain = float(mix["lead_in_s"]), float(mix["drain_s"])
    out, t0 = [], 0.0
    for length in (lead, float(seconds), drain):
        n = int(round(mix["rate_rps"] * length))
        if n:
            out.append((t0, length, n))
        t0 += length
    return out


def make_requests(mix: dict, seed: int, seconds: float) -> List[Arrival]:
    """The run's requests, in due order."""
    shape = np.random.default_rng([int(mix["shape_seed"]), 0])
    ids = np.random.default_rng([int(mix["shape_seed"]), 2])
    order = np.random.default_rng([seed, 0])
    out: List[Arrival] = []
    for start, length, n in parts(mix, seconds):
        plens = draw_lengths(mix["prompt"], shape, n)
        olens = draw_lengths(mix["output"], shape, n)
        prompts = [ids.integers(0, mix["id_hi"], int(p)).astype(np.int32)
                   for p in plens]
        # n exponential gaps scaled to sum to the part's length: the
        # arrival times of a Poisson process given its count in the part
        gaps = shape.exponential(1.0, n)
        gaps *= length / gaps.sum()
        dues = start + np.cumsum(order.permutation(gaps)) - gaps.min() / 2
        base = len(out)
        for i, j in enumerate(order.permutation(n)):
            out.append(Arrival(base + i, float(dues[i]), prompts[j],
                               int(olens[j]), key=base + int(j)))
    return out


def probe_requests(mix: dict, n: int) -> List[Arrival]:
    """Requests of the mix for the gear's calibration: the same in every
    run, under a stream that the measured requests never use."""
    rng = np.random.default_rng([int(mix["shape_seed"]), 1])
    plens = draw_lengths(mix["prompt"], rng, n)
    olens = draw_lengths(mix["output"], rng, n)
    return [Arrival(i, 0.0, rng.integers(0, mix["id_hi"], int(p))
                    .astype(np.int32), int(o), key=i)
            for i, (p, o) in enumerate(zip(plens, olens))]
