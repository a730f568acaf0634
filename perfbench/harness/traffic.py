"""The one general traffic generator: a traffic file's parameters and a
seed in, the work of one run out.

Kinds (``"kind"`` in the traffic file):

* ``open_loop`` — independent users. The count of requests is
  ``round(rate * span)`` in every seed; their due times are that many
  seeded uniform draws over the span, sorted (a Poisson process
  conditioned on its count); prompt and output lengths are the
  equal-probability quantiles of the two distributions — the same
  multiset in every seed — dealt to the arrivals by seeded permutations.
  An optional ``preload`` fills the lanes before the window.
* ``closed_loop`` — ``clients`` callers, each sending its next request
  when the last returned, from a pool of requests with the same
  multiset of lengths in every seed, dealt in rounds that each span the
  whole range of lengths.
* ``train_job`` — a fixed batch made on the device from the seed; the
  file carries the batch geometry only.

The seed changes order and instants, never the work offered. Pure numpy:
the program receives only the generated inputs.
"""

import dataclasses
import json
import math
import os
import statistics
from typing import List, Optional

import numpy as np

@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_tokens: int
    due_s: Optional[float] = None       # open loop: offset into the span
    seed: int = 0
    sampling: Optional[dict] = None     # temperature / top_p / top_k
    deadline_ms: Optional[float] = None


def load(bench_dir: str, name: str) -> dict:
    path = os.path.join(bench_dir, "traffic", name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no traffic file {name}.json under {bench_dir}/traffic/")
    with open(path) as f:
        return json.load(f)


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """``n`` equal-probability quantiles of a length distribution, at the
    mid-points ``(i + 0.5) / n``: the same multiset for every seed."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        elif dist["dist"] == "uniform":
            x = lo + u * (hi - lo)
        elif dist["dist"] == "fixed":
            x = dist["value"]
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _deal(lengths: List[int], rng: np.random.Generator,
          strata: int) -> np.ndarray:
    """The sorted ``lengths`` in a seeded order. With ``strata`` > 1 the
    order is made of rounds of ``strata`` requests that each hold one
    length from every stratum of the multiset (a stratum is a run of
    ``len / strata`` neighbours), so any few consecutive rounds carry
    nearly the same work whatever the seed."""
    v = np.asarray(sorted(lengths))
    if strata <= 1 or len(v) % strata:
        return v[rng.permutation(len(v))]
    per = len(v) // strata
    columns = [v[k * per:(k + 1) * per][rng.permutation(per)]
               for k in range(strata)]
    rounds = np.stack(columns, axis=1)                 # (per, strata)
    return np.concatenate([row[rng.permutation(strata)] for row in rounds])


def _requests(traffic: dict, vocab: int, n: int, seed: int, stream: int,
              strata: int = 1) -> List[Request]:
    rng = _rng(seed, stream)
    prompts = _deal(quantile_lengths(traffic["prompt_tokens"], n), rng,
                    strata)
    outputs = _deal(quantile_lengths(traffic["output_tokens"], n), rng,
                    strata)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    return [Request(prompt=rng.integers(0, vocab, int(p)).tolist(),
                    max_tokens=int(o), seed=int(s),
                    sampling=traffic.get("sampling"),
                    deadline_ms=traffic.get("deadline_ms"))
            for p, o, s in zip(prompts, outputs, seeds)]


def open_loop(traffic: dict, vocab: int, seed: int,
              span_s: float) -> List[Request]:
    n = int(round(traffic["rate_per_s"] * span_s))
    reqs = _requests(traffic, vocab, n, seed, 1)
    due = np.sort(_rng(seed, 2).uniform(0.0, span_s, n))
    for r, d in zip(reqs, due):
        r.due_s = float(d)
    return reqs


def preload(traffic: dict, vocab: int, seed: int,
            prefill_chunk: int) -> List[Request]:
    """The requests that fill the lanes before an open-loop window: all
    sent at once, prefilled in order, one chunk an iteration. Request
    ``i`` is given the tokens it decodes while the later ones prefill,
    plus a remainder spread evenly from ``retire_from`` to ``retire_to``
    tokens, so the preloaded lanes retire at staggered times inside the
    window, as lanes of a steady state do."""
    pre = traffic.get("preload")
    if not pre:
        return []
    n, p = int(pre["count"]), int(pre["prompt_tokens"])
    chunks = -(-p // prefill_chunk)
    rng = _rng(seed, 3)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    out = []
    for i in range(n):
        frac = i / (n - 1) if n > 1 else 0.0
        stagger = pre["retire_from"] + frac * (pre["retire_to"]
                                               - pre["retire_from"])
        out.append(Request(
            prompt=rng.integers(0, vocab, p).tolist(),
            max_tokens=int(chunks * (n - 1 - i) + round(stagger)),
            seed=int(seeds[i]), sampling=traffic.get("sampling"),
            deadline_ms=traffic.get("deadline_ms")))
    return out


def closed_loop(traffic: dict, vocab: int, seed: int) -> List[List[Request]]:
    """One list of requests per client, dealt round-robin from the pool,
    so that request ``k`` of every client belongs to round ``k`` (see
    :func:`_deal`). The pool is sized to outlast the run; a client that
    still exhausts its list starts it again."""
    clients = int(traffic["clients"])
    reqs = _requests(traffic, vocab, int(traffic["pool"]), seed, 1,
                     strata=clients)
    return [reqs[c::clients] for c in range(clients)]
