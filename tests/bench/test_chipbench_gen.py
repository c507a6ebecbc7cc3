"""The benchmark's traffic generator (bench/gen.py)."""
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import gen  # noqa: E402
from bench.manifest import Manifest  # noqa: E402

MOLHIV = Manifest().traffic("molhiv-closed32")


def test_molecule_sizes_mean_over_molhiv_count():
    sizes = gen.molecule_sizes(np.random.default_rng(7), 41127,
                               MOLHIV["atoms_mean"], MOLHIV["atoms_sigma"],
                               MOLHIV["atoms_min"], MOLHIV["atoms_max"])
    assert abs(sizes.mean() - 25.5) < 0.5
    assert sizes.min() >= 6 and sizes.max() <= 222
    # heavy tail: the 99th percentile is well over twice the median
    assert np.percentile(sizes, 99) > 2.2 * np.median(sizes)


def test_molecule_pool_is_deterministic_per_seed():
    p = dict(MOLHIV, pool_requests=3, molecules_per_request=5)
    a, b = gen.molecule_pool(p), gen.molecule_pool(p)
    c = gen.molecule_pool(dict(p, dataset_seed=p["dataset_seed"] + 1))
    flat = lambda pool: [(n, s.tolist(), d.tolist())  # noqa: E731
                         for req in pool for n, s, d in req]
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)
    assert len(a) == 3 and all(len(r) == 5 for r in a)


def test_molecules_are_simple_undirected_graphs():
    p = dict(MOLHIV, pool_requests=8, molecules_per_request=32)
    atoms = bonds = 0
    for req in gen.molecule_pool(p):
        for n, src, dst in req:
            assert (src != dst).all() and src.max() < n and dst.max() < n
            pairs = set(zip(src.tolist(), dst.tolist()))
            assert len(pairs) == len(src)                  # no repeats
            assert pairs == {(d, s) for s, d in pairs}     # both directions
            # connected: a spanning tree is inside
            seen, todo = {0}, [0]
            adj = {}
            for s, d in pairs:
                adj.setdefault(s, []).append(d)
            while todo:
                for w in adj.get(todo.pop(), []):
                    if w not in seen:
                        seen.add(w)
                        todo.append(w)
            assert len(seen) == n
            atoms += n
            bonds += len(src) // 2
    assert abs(2 * bonds / atoms - 2.16) < 0.05


def test_hilbert_index_walks_the_grid_one_step_at_a_time():
    xs, ys = (a.ravel() for a in np.meshgrid(np.arange(16), np.arange(16)))
    d = gen.hilbert_index(xs, ys, 4)
    assert sorted(d.tolist()) == list(range(256))
    order = np.argsort(d)
    steps = np.abs(np.diff(xs[order])) + np.abs(np.diff(ys[order]))
    assert (steps == 1).all()


def test_geometric_graph_is_a_simple_undirected_graph_of_its_size():
    V, E = 2000, 4800
    src, dst = gen.geometric_graph(V, E, 5)
    assert src.dtype == dst.dtype == np.int32
    assert len(src) == 2 * E                     # each edge both ways
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert len(pairs) == 2 * E                   # no repeats
    assert pairs == {(d, s) for s, d in pairs}
    assert (src != dst).all() and src.min() >= 0 and src.max() < V


def test_geometric_graph_is_deterministic_per_seed():
    a = gen.geometric_graph(1000, 2400, 3)
    b = gen.geometric_graph(1000, 2400, 3)
    c = gen.geometric_graph(1000, 2400, 4)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not all((x == y).all() for x, y in zip(a, c))


def test_geometric_graph_keeps_neighbours_close_in_id():
    """Hilbert numbering: on a 16x16 grid of id ranges most edges fall into
    tiles near the diagonal, and most tiles stay empty."""
    V, E = 20000, 48000
    src, dst = gen.geometric_graph(V, E, 0)
    part = 16 * np.arange(V) // V
    tiles = set(zip(part[dst].tolist(), part[src].tolist()))
    assert len(tiles) < 16 * 16 / 3
    assert np.mean(part[src] == part[dst]) > 0.8
