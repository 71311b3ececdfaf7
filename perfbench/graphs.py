"""Graph generators of the benchmark's configurations, built on the host.

Each generator returns plain numpy COO arrays ``(src, dst, w, n)``: the
harness hands them to the program's ``from_coo`` and the reference reads
them directly, so the reference never sees anything the program made.
A configuration file names its generator under ``"generator"`` and gives
its parameters as keys of their own; a new configuration that uses one of
these generators adds only a data file.
"""
from __future__ import annotations

import inspect

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): the weights, the query
    support and the queries each draw from their own stream of one seed."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def grid_road(rng: np.random.Generator, wrng: np.random.Generator,
              side: int, diag_frac: float):
    """Bidirected ``side x side`` 4-neighbour grid plus ``diag_frac * n``
    down-right diagonal shortcuts, every arc with its own U[0, 1) weight.
    No shipped configuration uses it: it is the small graph of the tests'
    open-loop cell and of the recorded trace."""
    n = side * side
    vid = np.arange(n, dtype=np.int64).reshape(side, side)
    src = [vid[:, :-1].ravel(), vid[:-1, :].ravel()]
    dst = [vid[:, 1:].ravel(), vid[1:, :].ravel()]
    nd = int(diag_frac * n)
    r = rng.integers(0, side - 1, nd)
    c = rng.integers(0, side - 1, nd)
    src.append(vid[r, c])
    dst.append(vid[r + 1, c + 1])
    s, d = np.concatenate(src), np.concatenate(dst)
    s, d = np.concatenate([s, d]), np.concatenate([d, s])
    w = wrng.random(s.shape[0], dtype=np.float32)
    return s.astype(np.int32), d.astype(np.int32), w, n


def graph500(rng: np.random.Generator, wrng: np.random.Generator,
             scale: int, edgefactor: int, a: float, b: float, c: float):
    """Graph500 Kronecker edge list (the specification's generator): for
    each of ``edgefactor * 2**scale`` edges and each bit, the row bit is 1
    with probability ``1 - (a + b)`` and the column bit with ``c / (c + d)``
    or ``b / (a + b)`` given it; vertex labels are then permuted at random.
    Each undirected edge becomes two arcs with one U[0, 1) weight; self
    loops are dropped, repeated edges are kept as the specification makes
    them."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        u |= ii.astype(np.int64) << bit
        v |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    u, v = perm[u], perm[v]
    keep = u != v
    u, v = u[keep], v[keep]
    w = wrng.random(u.shape[0], dtype=np.float32)
    s, d = np.concatenate([u, v]), np.concatenate([v, u])
    return s.astype(np.int32), d.astype(np.int32), np.concatenate([w, w]), n


GENERATORS = {"grid_road": grid_road, "graph500": graph500}


def build(config: dict, seed: int):
    """``(src, dst, w, n, labels)`` of a configuration's graph for one seed.

    The generator's parameters are the configuration's keys of the same
    name. The arcs and their weights come from the configuration's
    ``structure_seed``, so every run serves one graph, as a deployment does.
    The run's seed relabels its vertices by a random permutation (the
    Graph500 generator's own last step): every seed serves an isomorphic
    copy, with other arrays but the same degrees, layout shapes, compiled
    programs and phases per root. ``labels[v]`` is the served label of the
    generator's vertex ``v``; the traffic draws its roots through it, so
    every seed asks the same questions of its copy."""
    gen = GENERATORS[config["generator"]]
    names = list(inspect.signature(gen).parameters)[2:]
    structure = config["structure_seed"]
    src, dst, w, n = gen(rng_for(structure, 0), rng_for(structure, 4),
                         **{k: config[k] for k in names})
    labels = rng_for(seed, 0).permutation(n).astype(np.int32)
    return labels[src], labels[dst], w, n, labels
