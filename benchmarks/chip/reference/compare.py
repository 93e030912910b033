"""The comparison that decides ``correct``.

Once the window has closed, a sample drawn from the seed of the requests
the run finished (the longest among them) is run through the plain
reference, one request at a time: prompt plus served tokens, the served
tokens taken as given. At each served position two numbers are read:

* ``logit_shortfall``: how far the reference's logit of the served token
  lies below the reference's best logit there (0 where they agree);
* ``gap_error``: how far the served top-1 minus top-2 gap (the certainty
  that drives escalation, from the top-2-gap kernel) lies from the
  reference's.

Each is the widest over the sample, per stage model. The reference is the
stage model's family's (``families/<family>.py``: ``hidden``, ``stats``).
The precision control (``quant``) runs the reference in int8 or fp8 in the
program's place and reads the same two numbers for the tokens and gaps
that it puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def score(family, a, params, prompt: np.ndarray, served: Sequence[int],
          gaps: Sequence[float], controls: Sequence[str] = ()) -> dict:
    served = np.asarray(served, np.int32)
    gaps = np.asarray(gaps, np.float64)
    p, n = prompt.size, served.size
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = slice(p - 1, p - 1 + n)
    x = family.hidden(a, params, seq)
    target = np.full(x.shape[0], -1, np.int32)
    target[rows] = served
    short, gap, _ = family.stats(a, params, x, target)
    out = {"tokens": int(n),
           "logit_shortfall": float(short[rows].max()),
           "gap_error": float(np.abs(gap[rows] - gaps).max())}
    for q in controls:
        xc = family.hidden(a, params, seq, quant=q)
        _, gap_c, pick = family.stats(a, params, xc, target, quant=q)
        target_c = np.full_like(target, -1)
        target_c[rows] = pick[rows]
        short_c, _, _ = family.stats(a, params, x, target_c)
        out[f"{q}.logit_shortfall"] = float(short_c[rows].max())
        out[f"{q}.gap_error"] = float(np.abs(gap_c[rows] - gap[rows]).max())
    return out


def sample(done: List[dict], seed: int, min_tokens: int,
           max_requests: int, min_requests: int = 1) -> List[dict]:
    """The longest finished request (prompt plus output), then others drawn
    from the seed until ``min_tokens`` served tokens and ``min_requests``
    requests, or ``max_requests``."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r["rid"])
    longest = max(done, key=lambda r: (r["prompt"].size + len(r["tokens"]),
                                       -r["rid"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    picked, n = [longest], len(longest["tokens"])
    for i in order:
        if (n >= min_tokens and len(picked) >= min_requests) \
                or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i]["tokens"])
    return picked


def compare(stages: Sequence[str], models: Dict[str, Tuple[object, object]],
            params: list,
            done_by_stage: Dict[int, List[dict]], seed: int,
            min_tokens: int, max_requests: int, min_requests: int = 1,
            controls: Sequence[str] = ()) -> Dict[str, dict]:
    """Per stage model: the widest readings over its sample, and the
    number of requests and tokens compared. ``models`` gives each stage
    model's (family module, arch)."""
    out = {}
    for si, name in enumerate(stages):
        picked = sample(done_by_stage.get(si, []), seed + si, min_tokens,
                        max_requests, min_requests)
        if not picked:
            continue
        family, a = models[name]
        rows = [score(family, a, params[si], r["prompt"], r["tokens"],
                      r["gaps"], controls) for r in picked]
        agg = {k: max(r[k] for r in rows) for k in rows[0]
               if k != "tokens"}
        agg["requests"] = len(rows)
        agg["tokens"] = sum(r["tokens"] for r in rows)
        out[name] = agg
    return out
