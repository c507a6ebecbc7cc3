"""An FB15k-237-shaped knowledge graph (Toutanova & Chen 2015) from a traffic
file's parameters: pure numpy, fixed by ``dataset_seed`` like the dataset it
stands for, and importing nothing of the program.

* exactly ``entities`` entities and ``relations`` relations, each relation
  with at least one triple;
* exactly ``triples`` distinct (head, relation, tail) triples, no self-loops;
* heavy-tailed entity degrees: heads and tails drawn with weight
  ``rank ** -entity_zipf`` over a random ranking of the entities;
* skewed relation sizes: relation ``r`` (by rank) holds a share
  ``(r + 1) ** -relation_zipf`` of the triples;
* every triple stored both ways, as R-GCN's encoder reads it: ``h -> t`` of
  type ``r`` and ``t -> h`` of type ``r + relations``.
"""
from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

TypedEdges = Tuple[np.ndarray, np.ndarray, np.ndarray]


def relation_sizes(n_triples: int, n_relations: int,
                   zipf: float) -> np.ndarray:
    """Triples per relation: Zipf shares rounded to sum to ``n_triples``,
    each at least 1 (largest remainders take the rest)."""
    share = np.arange(1, n_relations + 1, dtype=np.float64) ** -zipf
    share /= share.sum()
    free = n_triples - n_relations
    raw = free * share
    sizes = np.floor(raw).astype(np.int64)
    rest = free - int(sizes.sum())
    sizes[np.argsort(sizes - raw, kind="stable")[:rest]] += 1
    return sizes + 1


def kg_graph(p: dict, log: Callable = print) -> TypedEdges:
    """(src, dst, edge_type) int32 of the traffic file's knowledge graph,
    every triple in both directions (``2 * triples`` typed edges)."""
    V, R = int(p["entities"]), int(p["relations"])
    rng = np.random.default_rng(int(p["dataset_seed"]))
    weight = np.arange(1, V + 1, dtype=np.float64) ** -float(p["entity_zipf"])
    weight = weight[rng.permutation(V)]
    weight /= weight.sum()
    sizes = relation_sizes(int(p["triples"]), R, float(p["relation_zipf"]))
    heads, tails, rels = [], [], []
    for r, n in enumerate(sizes):
        got = np.empty(0, np.int64)        # h * V + t, in draw order
        while got.size < n:
            want = 2 * (n - got.size) + 16
            h = rng.choice(V, want, p=weight)
            t = rng.choice(V, want, p=weight)
            key = np.concatenate([got, (h * V + t)[h != t]])
            _, first = np.unique(key, return_index=True)
            got = key[np.sort(first)]
        got = got[:n]
        heads.append(got // V)
        tails.append(got % V)
        rels.append(np.full(n, r, np.int64))
    h, t, r = (np.concatenate(a) for a in (heads, tails, rels))
    src = np.concatenate([h, t]).astype(np.int32)
    dst = np.concatenate([t, h]).astype(np.int32)
    etype = np.concatenate([r, r + R]).astype(np.int32)
    deg = np.bincount(dst, minlength=V)
    log(f"[graph] {V} entities, {R} relations, {len(h)} triples, "
        f"{len(src)} typed edges; largest in-degree {int(deg.max())}, "
        f"{int(np.count_nonzero(deg == 0))} entities without an edge; "
        f"largest relation {int(sizes.max())} triples, smallest "
        f"{int(sizes.min())}")
    return src, dst, etype
