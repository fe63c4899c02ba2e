"""The one traffic generator: turns a mix's parameters and a seed into requests.

A mix (``bench/traffic/<name>.json``) holds data only:

  ``loop``        ``"closed"`` (one client sends its next request when the last
                  one is answered) or ``"open"`` (requests are due on a schedule,
                  answered or not);
  ``rate_per_s``  open loop only: offered requests per second;
  ``arrivals_seed`` open loop only: the seed of the arrival times;
  ``query``       what each request asks; the configuration's family reads it;
  ``pick``        optional per-request parameters, ``{key: {"values": [...],
                  "zipf": s}}``: value i (in rank order) is asked with weight
                  (i + 1)^-s.

Every seed gets the same work in another order: the number of requests is
``round(rate_per_s * seconds)``, each parameter value is asked the same number
of times (its weight's share, apportioned by largest remainder), and the
arrival times are the same for every seed: that many points drawn uniformly over
the window from ``arrivals_seed`` and sorted (a Poisson process conditioned on
its count). Only which request arrives at which time comes from ``--seed``. A
queue's tail depends on how arrivals bunch, far more than on anything the system
does, so times drawn from each seed would make the tail swing from seed to seed.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

#: closed-loop parameter sequences repeat after this many requests.
CLOSED_CYCLE = 4096


@dataclass
class Schedule:
    loop: str
    query: dict
    params: List[Dict]                    # one per request, in sending order
    due_s: Optional[List[float]] = None   # open loop: seconds after the window opens
    distinct: List[Dict] = field(default_factory=list)


def apportion(weights: List[float], n: int) -> List[int]:
    """Split ``n`` requests over ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    if n < 0 or w.size == 0 or np.any(w < 0) or w.sum() <= 0:
        raise ValueError("need n >= 0 and non-negative weights with a positive sum")
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.lexsort((np.arange(w.size), -(exact - counts)))
    counts[order[:rest]] += 1
    return counts.tolist()


def _pick_sequence(spec: dict, n: int, rng: np.random.Generator) -> list:
    values = list(spec["values"])
    s = float(spec.get("zipf", 0.0))
    weights = [(i + 1.0) ** -s for i in range(len(values))]
    seq = [v for v, c in zip(values, apportion(weights, n)) for _ in range(c)]
    return [seq[i] for i in rng.permutation(len(seq))]


def make_schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    loop = mix["loop"]
    rng = np.random.default_rng(seed)
    if loop == "open":
        n = int(round(float(mix["rate_per_s"]) * seconds))
        if n < 1:
            raise ValueError("an open loop needs at least one request in the window")
        arrivals = np.random.default_rng(int(mix["arrivals_seed"]))
        due = np.sort(arrivals.uniform(0.0, seconds, size=n)).tolist()
    elif loop == "closed":
        n, due = CLOSED_CYCLE, None
    else:
        raise ValueError(f"unknown loop {loop!r}")
    picks = mix.get("pick", {})
    columns = {key: _pick_sequence(spec, n, rng) for key, spec in sorted(picks.items())}
    params = [{key: col[i] for key, col in columns.items()} for i in range(n)]
    distinct = [dict(t) for t in sorted({tuple(sorted(p.items())) for p in params})]
    return Schedule(loop=loop, query=dict(mix["query"]), params=params, due_s=due,
                    distinct=distinct)
