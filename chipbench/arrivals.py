"""The one traffic generator: turns a mix file (`traffic/<mix>.json`) and a
seed into the requests of a run.

A mix is data.  Its `loop` is `closed` or `open`; its `requests` list the
request kinds, each either a spec query (`{"query": "q5"}`, served with the
spec's validation values) or a template with a pool of bindings
(`{"template": "q6", "weight": 1.0, "bindings": [{...}, ...]}`).  A string
binding that reads as an ISO date is sent as days since 1970-01-01.

* closed: rounds of every request kind, each round in an order drawn from
  the seed; one client sends the next request when the last one answered,
  and the window ends with the round in which the run's seconds ran out,
  so every run does whole rounds of the same work.
* open: `rate_per_s` arrivals a second for the run's seconds, replayed
  from one schedule that the mix's `schedule_seed` fixes: the count of
  each template by weight (largest remainder), the inter-arrival gaps (the
  quantiles of the exponential distribution, so the arrivals are
  Poisson-like) and the templates' order.  `--seed` only deals each
  template's bindings, every binding of a pool equally often, to that
  template's arrivals.  Queueing makes the tails follow the order of slow
  requests, so a seed that reordered them would change the work.
  Latency is timed from each request's due time.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from chipbench.tpch_data import days

_DATE = re.compile(r"^\d{4}-\d{2}-\d{2}$")


@dataclasses.dataclass(frozen=True)
class Request:
    """One request kind: a query name, whether it is sent as the
    parameterized template, and its bindings (None for a spec query)."""
    query: str
    template: bool
    bindings: tuple | None

    @property
    def label(self) -> str:
        if self.bindings is None:
            return self.query
        return self.query + "{" + ",".join(
            f"{k}={v}" for k, v in self.bindings) + "}"

    def binding_dict(self) -> dict | None:
        return None if self.bindings is None else dict(self.bindings)


def _binding(raw: dict) -> tuple:
    return tuple(sorted(
        (k, days(v) if isinstance(v, str) and _DATE.match(v) else v)
        for k, v in raw.items()))


def kinds(mix: dict) -> list[Request]:
    """Every distinct request the mix can send, in file order."""
    out = []
    for r in mix["requests"]:
        if "query" in r:
            out.append(Request(r["query"], False, None))
        else:
            out += [Request(r["template"], True, _binding(b))
                    for b in r["bindings"]]
    return out


def closed_rounds(mix: dict, seed: int):
    """Endless stream of rounds: every request kind once per round, each
    round shuffled by the seed."""
    reqs = kinds(mix)
    rng = np.random.default_rng(seed)
    while True:
        yield [reqs[i] for i in rng.permutation(len(reqs))]


def _shares(weights: np.ndarray, n: int) -> np.ndarray:
    """Counts summing to n in proportion to weights (largest remainder)."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(int)
    short = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def open_schedule(mix: dict, seed: int, seconds: float,
                  rate: float | None = None) -> list[tuple[float, Request]]:
    """(due offset in seconds, request) for every arrival of the window."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(mix["schedule_seed"])
    deal = np.random.default_rng(seed)
    templates = mix["requests"]
    weights = np.array([float(t.get("weight", 1.0)) for t in templates])
    counts = _shares(weights, n)
    order = fixed.permutation(np.repeat(np.arange(len(templates)), counts))
    q = (np.arange(n) + 0.5) / n
    gaps = fixed.permutation(-np.log1p(-q) / rate)
    due = np.cumsum(gaps) - gaps[0]
    picks: list = [None] * n
    for k, t in enumerate(templates):
        pool = [Request(t["template"], True, _binding(b))
                for b in t["bindings"]]
        slots = np.flatnonzero(order == k)
        dealt = deal.permutation(len(slots)) % len(pool)
        for i, j in zip(slots, dealt):
            picks[i] = pool[j]
    return [(float(t), r) for t, r in zip(due, picks)]
