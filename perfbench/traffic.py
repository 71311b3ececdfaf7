"""The one traffic generator: every mix is a data file of parameters.

Keys of ``perfbench/traffic/<name>.json``:

- ``loop``: ``"open"`` (independent users on a schedule) or ``"closed"``
  (a batch client that keeps ``outstanding`` requests in the system);
- ``rate_per_s`` (open loop): arrivals per second. A run of ``seconds``
  holds exactly ``round(rate_per_s * seconds)`` arrivals, a Poisson process
  conditioned on its count;
- ``outstanding`` (closed loop): requests kept in the system, reached from
  the window's empty start one request per scheduling round
  (``serve.run_window``);
- ``st_share``: share of s->t queries; the rest are full rows;
- ``pool``: ``"all"`` vertices or those of ``"nonzero_degree"``;
- ``support``: how many seeded vertices of the pool queries draw from
  (``null``: the whole pool);
- ``zipf_s``: Zipf exponent over the support's ranks (``null``: uniform);
  targets of s->t queries are drawn like sources, independently;
- ``cache_rows``: capacity of the server's ``DistCache``.

The queries, the Zipf support and the inter-arrival gaps are drawn from the
configuration's ``structure_seed`` over the generator's vertex ids, and
mapped through the run's relabelling of the graph (``graphs.build``). So
every seed sends the same requests in the same order, each naming its own
copy's labels: the same work, which a reordering would not give where a
cache answers a share that depends on the order.
"""
from __future__ import annotations

import numpy as np

from perfbench.graphs import rng_for

FULL = -1  # target of a full-row query


class Traffic:
    """Queries of one mix over a graph with ``n`` vertices, drawn from
    ``set_seed``. ``out_degree`` is by served label; ``labels[v]`` is the
    served label of the generator's vertex ``v`` (the identity when not
    given)."""

    def __init__(self, params: dict, n: int, out_degree: np.ndarray,
                 set_seed: int, labels: np.ndarray | None = None):
        self.params = params
        if labels is None:
            labels = np.arange(n)
        pick = rng_for(set_seed, 1)
        pool = np.arange(n)
        if params.get("pool", "all") == "nonzero_degree":
            pool = np.nonzero(np.asarray(out_degree)[labels] > 0)[0]
        support = params.get("support")
        if support is not None:
            pool = pick.choice(pool, size=int(support), replace=False)
        self.support = labels[pool]  # in rank order when Zipf-drawn
        zipf_s = params.get("zipf_s")
        self.p = None
        if zipf_s is not None:
            ranks = np.arange(1, pool.size + 1, dtype=np.float64)
            p = ranks ** -float(zipf_s)
            self.p = p / p.sum()
        self._rng = rng_for(set_seed, 2)  # the queries and gaps offered

    def draw(self, count: int) -> tuple[np.ndarray, np.ndarray]:
        """``count`` queries: (sources, targets), ``FULL`` for full rows."""
        rng = self._rng
        src = self.support[rng.choice(self.support.size, count, p=self.p)]
        tgt = self.support[rng.choice(self.support.size, count, p=self.p)]
        is_st = rng.random(count) < float(self.params.get("st_share", 0.0))
        return src.astype(np.int64), np.where(is_st, tgt, FULL).astype(np.int64)

    def schedule(self, seconds: float):
        """Open loop: (times from the window's start, sources, targets).
        The times are exponential gaps scaled to span the window: a Poisson
        process conditioned on its count."""
        count = int(round(float(self.params["rate_per_s"]) * seconds))
        gaps = self._rng.exponential(size=count + 1)
        times = np.cumsum(gaps)[:count] * (seconds / gaps.sum())
        src, tgt = self.draw(count)
        return times, src, tgt
