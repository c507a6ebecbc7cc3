"""The traffic generator: graphs and request pools from a traffic file's
parameters and a seed.  Pure numpy and scipy; it imports nothing of the
program, so a program change cannot change the work.

* ``geometric_graph`` — a random geometric graph (Penrose, "Random
  Geometric Graphs", 2003; the construction of the DIMACS10 ``rgg``
  family): points uniform in the unit square, the closest pairs joined,
  every edge stored in both directions, vertices numbered along a Hilbert
  curve so that ids keep neighbours close, as a map's region ids do.
* ``molecules`` — heavy-tailed molecule-like graphs shaped after OGB
  ogbg-molhiv: lognormal atom counts, a spanning tree plus ring closures,
  every bond stored in both directions.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np

Edges = Tuple[np.ndarray, np.ndarray]


def hilbert_index(x: np.ndarray, y: np.ndarray, order: int) -> np.ndarray:
    """Position along the Hilbert curve of the cells ``(x, y)`` of a
    ``2**order`` square grid."""
    n = 1 << order
    x, y = x.astype(np.int64), y.astype(np.int64)
    d = np.zeros_like(x)
    s = n >> 1
    while s:
        rx, ry = (x & s) > 0, (y & s) > 0
        d += s * s * ((3 * rx) ^ ry)
        flip = ~ry & rx
        x, y = np.where(flip, n - 1 - x, x), np.where(flip, n - 1 - y, y)
        x, y = np.where(ry, x, y), np.where(ry, y, x)
        s >>= 1
    return d


def geometric_graph(n_vertices: int, n_edges: int, seed: int) -> Edges:
    """(src, dst) int32 arrays of a random geometric graph: ``n_vertices``
    points uniform in the unit square, the ``n_edges`` closest pairs joined
    (a radius set by the edge count), each edge stored in both directions,
    so ``2 * n_edges`` directed edges.  Vertices are numbered along a
    Hilbert curve over the square."""
    from scipy.spatial import cKDTree

    rng = np.random.default_rng(seed)
    pts = rng.random((n_vertices, 2))
    pairs_in_reach = n_vertices * (n_vertices - 1) / 2 * math.pi
    r = 1.3 * math.sqrt(n_edges / pairs_in_reach)
    while True:
        pairs = cKDTree(pts).query_pairs(r, output_type="ndarray")
        if len(pairs) >= n_edges:
            break
        r *= 1.5
    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    dist = np.linalg.norm(pts[pairs[:, 0]] - pts[pairs[:, 1]], axis=1)
    u, v = pairs[np.argsort(dist, kind="stable")[:n_edges]].T
    cell = np.minimum((pts * 65536).astype(np.int64), 65535)
    order = np.argsort(hilbert_index(cell[:, 0], cell[:, 1], 16),
                       kind="stable")
    vid = np.empty(n_vertices, np.int64)
    vid[order] = np.arange(n_vertices)
    u, v = vid[u], vid[v]
    return (np.concatenate([u, v]).astype(np.int32),
            np.concatenate([v, u]).astype(np.int32))


def molecule_sizes(rng: np.random.Generator, n: int, mean: float,
                   sigma: float, lo: int, hi: int) -> np.ndarray:
    """Atom counts: lognormal with the given mean and log-space ``sigma``,
    rounded and clipped to [lo, hi]."""
    mu = math.log(mean) - sigma * sigma / 2.0
    sizes = np.rint(rng.lognormal(mu, sigma, size=n)).astype(np.int64)
    return np.clip(sizes, lo, hi)


def molecule(rng: np.random.Generator, n_atoms: int,
             bonds_per_atom: float) -> Edges:
    """One molecule-like graph: a spanning tree in which atom ``i`` bonds to
    one of the four atoms before it, plus ring closures ``(i, i-5)``
    (six-membered rings) up to ``round(n_atoms * bonds_per_atom)`` bonds.
    No self-loops and no repeated bonds; both directions are stored."""
    parents = np.arange(n_atoms - 1) - rng.integers(
        0, np.minimum(np.arange(1, n_atoms), 4))
    child = np.arange(1, n_atoms)
    n_rings = max(0, int(round(n_atoms * bonds_per_atom)) - (n_atoms - 1))
    n_rings = min(n_rings, max(n_atoms - 5, 0))
    ring_i = np.sort(rng.choice(np.arange(5, n_atoms), size=n_rings,
                                replace=False)) if n_rings else \
        np.zeros(0, np.int64)
    u = np.concatenate([child, ring_i])
    v = np.concatenate([parents, ring_i - 5])
    src = np.concatenate([u, v]).astype(np.int32)
    dst = np.concatenate([v, u]).astype(np.int32)
    return src, dst


def molecule_pool(p: dict) -> List[List[Edges]]:
    """``p["pool_requests"]`` requests of ``p["molecules_per_request"]``
    molecules each, from ``p["dataset_seed"]``: a fixed set, like the
    dataset it stands for.  Returns ``pool[request][molecule] =
    (n_atoms, src, dst)``."""
    rng = np.random.default_rng(p["dataset_seed"])
    n = p["pool_requests"] * p["molecules_per_request"]
    sizes = molecule_sizes(rng, n, p["atoms_mean"], p["atoms_sigma"],
                           p["atoms_min"], p["atoms_max"])
    bpa = p["bonds_mean"] / p["atoms_mean"]
    mols = [(int(s),) + molecule(rng, int(s), bpa) for s in sizes]
    k = p["molecules_per_request"]
    return [mols[i:i + k] for i in range(0, n, k)]
