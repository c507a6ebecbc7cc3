"""CSR-within-tile layout + reorder provenance (§5.3 satellites).

Three invariant families pinned here:

* **CSR structure** — :func:`~repro.core.tiling.csr_tiles` produces monotone
  per-tile row pointers whose runs partition exactly the real edge slots,
  padded slots stay unreachable past ``row_ptr[t, -1]`` (so the kernels need
  no tail masking — asserted by poisoning the padding), and the byte model
  charges one column index per edge plus the row-pointer tables.
* **Reorder coverage** — out-degree sorting, degenerate graphs (zero-edge,
  single-vertex), and the permute/unpermute round trip (property-based when
  hypothesis is installed).
* **Cache isolation** — CSR vs COO tile sets and identity vs degree reorder
  modes always produce distinct ``structure_signature`` keys and distinct
  :class:`~repro.serve.signature.ShapeRegistry` registrations; a layout or
  reorder change can never silently reuse a compiled program.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reorder, tiling
from repro.gnn import graphs
from repro.kernels.tile_spmm import ops as tops
from repro.kernels.tile_spmm.kernel import tile_flags
from repro.kernels.tile_spmm.ref import (segment_softmax_csr_ref,
                                         tile_spmm_csr_ref)
from repro.serve.signature import ShapeRegistry, structure_signature


def _graph(v=120, e=500, seed=3):
    return graphs.random_graph(v, e, seed=seed, model="powerlaw")


# ---------------------------------------------------------------------------
# CSR tile structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,s", [(4, 4), (3, 5), (1, 1)])
def test_csr_row_ptr_partitions_real_edges(p, s):
    g = _graph()
    ts = tiling.grid_tile(g, p, s, sparse=True)
    cs = tiling.csr_tiles(ts)
    assert cs.layout == "csr" and cs.row_ptr is not None
    dmax = int(ts.part_size.max())
    assert cs.row_ptr.shape == (ts.n_tiles, dmax + 1)
    for t in range(cs.n_tiles):
        rp = cs.row_ptr[t]
        ne = int(cs.n_edge[t])
        assert rp[0] == 0 and rp[-1] == ne      # padded slots unreachable
        assert (np.diff(rp) >= 0).all()
        for d in range(dmax):
            run = cs.edge_dst[t, rp[d]:rp[d + 1]]
            assert (run == d).all(), (t, d)
        # same edges, same src/dst pairs — only the intra-tile order moved
        assert sorted(cs.edge_gid[t, :ne]) == sorted(ts.edge_gid[t, :ne])
        pairs = {(int(a), int(b)) for a, b in
                 zip(ts.edge_src[t, :ne], ts.edge_dst[t, :ne])}
        assert pairs == {(int(a), int(b)) for a, b in
                         zip(cs.edge_src[t, :ne], cs.edge_dst[t, :ne])}
    # idempotent, and grid_tile(layout=) is the same construction
    assert tiling.csr_tiles(cs) is cs
    direct = tiling.grid_tile(g, p, s, sparse=True, layout="csr")
    assert direct.shape_signature() == cs.shape_signature()
    np.testing.assert_array_equal(direct.row_ptr, cs.row_ptr)


def test_csr_edge_index_bytes_model():
    g = _graph()
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    cs = tiling.csr_tiles(ts)
    E = int(ts.n_edge.sum())
    assert ts.edge_index_bytes() == E * 8                   # (src, dst) pairs
    width = cs.row_ptr.shape[1]
    assert cs.edge_index_bytes() == E * 4 + cs.n_tiles * width * 4
    # the layouts diverge only in index traffic, not vertex traffic
    assert cs.src_vertex_loads() == ts.src_vertex_loads()


def test_grid_tile_rejects_unknown_layout():
    with pytest.raises(ValueError, match="layout"):
        tiling.grid_tile(_graph(), 2, 2, sparse=True, layout="ell")


# ---------------------------------------------------------------------------
# CSR kernels vs whole-graph oracles (padding poisoned on purpose)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True], ids=["ref", "pallas"])
def test_csr_spmm_matches_whole_graph(use_kernel, rng):
    g = _graph(100, 420, seed=5)
    cs = tiling.grid_tile(g, 4, 3, sparse=True, layout="csr")
    F = 16
    x = rng.standard_normal((g.n_vertices, F)).astype(np.float32)
    w_g = rng.standard_normal(g.n_edges).astype(np.float32)

    xs = tops.gather_sources(cs, x)
    w = w_g[cs.edge_gid].astype(np.float32)
    for t in range(cs.n_tiles):                 # poison padded edge slots:
        w[t, int(cs.n_edge[t]):] = 1e9          # row_ptr must never reach them
    args = (jnp.asarray(cs.row_ptr), jnp.asarray(cs.edge_src),
            jnp.asarray(w), xs, jnp.asarray(cs.part_id))
    if use_kernel:
        out = tops.spmm_csr(*args, jnp.asarray(tile_flags(cs.part_id)),
                            n_parts=cs.n_dst_parts)
    else:
        out = tile_spmm_csr_ref(*args, cs.n_dst_parts)

    whole = np.zeros((g.n_vertices, F), np.float32)
    np.add.at(whole, g.dst, w_g[:, None] * x[g.src])
    for p in range(cs.n_dst_parts):
        n, lo = int(cs.part_size[p]), int(cs.part_start[p])
        np.testing.assert_allclose(np.asarray(out)[p, :n], whole[lo:lo + n],
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["ref", "pallas"])
def test_csr_segment_softmax_matches_whole_graph(use_kernel, rng):
    g = _graph(80, 360, seed=7)
    cs = tiling.grid_tile(g, 3, 3, sparse=True, layout="csr")
    F = 8
    s_g = rng.standard_normal(g.n_edges).astype(np.float32)
    v_g = rng.standard_normal((g.n_edges, F)).astype(np.float32)

    scores = s_g[cs.edge_gid].astype(np.float32)
    vals = v_g[cs.edge_gid].astype(np.float32)
    for t in range(cs.n_tiles):
        scores[t, int(cs.n_edge[t]):] = 1e9     # poisoned padding again
    args = (jnp.asarray(cs.row_ptr), jnp.asarray(scores), jnp.asarray(vals),
            jnp.asarray(cs.part_id))
    if use_kernel:
        out = tops.gat_aggregate_csr(*args,
                                     jnp.asarray(tile_flags(cs.part_id)),
                                     n_parts=cs.n_dst_parts)
    else:
        out = segment_softmax_csr_ref(*args, cs.n_dst_parts)

    whole = np.zeros((g.n_vertices, F), np.float32)
    for v in np.unique(g.dst):
        e = np.nonzero(g.dst == v)[0]
        p = np.exp(s_g[e] - s_g[e].max())
        whole[v] = (p[:, None] * v_g[e]).sum(0) / p.sum()
    for p in range(cs.n_dst_parts):
        n, lo = int(cs.part_size[p]), int(cs.part_start[p])
        got = np.asarray(out)[p, :n]
        mask = np.isin(np.arange(lo, lo + n), g.dst)
        np.testing.assert_allclose(got[mask], whole[lo:lo + n][mask],
                                   atol=1e-4, rtol=1e-4)


def test_csr_refs_agree_with_each_other(rng):
    """The within-layout oracles agree with the kernel entry points on a
    bucketed batch."""
    g = _graph(90, 380, seed=11)
    ts, _ = tiling.build_tiles(g, 4, 4, reorder="degree", layout="csr",
                               n_buckets=2)
    for b in ts.buckets:
        F = 8
        x = rng.standard_normal((g.n_vertices, F)).astype(np.float32)
        xs = tops.gather_sources(b, x)
        w = rng.standard_normal(b.edge_src.shape).astype(np.float32)
        args = (jnp.asarray(b.row_ptr), jnp.asarray(b.edge_src),
                jnp.asarray(w), xs, jnp.asarray(b.part_id))
        ref = tile_spmm_csr_ref(*args, b.n_dst_parts)
        out = tops.spmm_csr(*args, jnp.asarray(tile_flags(b.part_id)),
                            n_parts=b.n_dst_parts)
        # partitions with no tile in this bucket are never flushed — the
        # runner masks them the same way before summing across buckets
        present = np.isin(np.arange(b.n_dst_parts), b.part_id)
        np.testing.assert_allclose(np.asarray(out)[present],
                                   np.asarray(ref)[present],
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# reorder coverage: out-degree sorting + degenerate graphs
# ---------------------------------------------------------------------------

def test_degree_sort_by_out_orders_out_degrees():
    g = _graph(150, 600, seed=2)
    ro = reorder.degree_sort(g, by="out")
    assert ro.mode == "degree-out"
    deg = ro.graph.out_degrees()
    assert (np.diff(deg) <= 0).all()            # non-increasing after sort
    # still the same graph up to relabeling
    assert ro.graph.n_edges == g.n_edges
    np.testing.assert_array_equal(ro.order[ro.rank],
                                  np.arange(g.n_vertices))
    np.testing.assert_array_equal(ro.order[ro.graph.src], g.src)
    np.testing.assert_array_equal(ro.order[ro.graph.dst], g.dst)


def test_degree_sort_rejects_unknown_axis():
    with pytest.raises(ValueError, match="'in' or 'out'"):
        reorder.degree_sort(_graph(), by="total")


@pytest.mark.parametrize("by", ["in", "out"])
def test_degree_sort_zero_edge_graph(by):
    g = graphs.Graph(src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
                     n_vertices=6, name="empty")
    ro = reorder.degree_sort(g, by=by)
    # all-equal degrees: the stable sort is the identity permutation
    np.testing.assert_array_equal(ro.order, np.arange(6))
    assert ro.graph.n_edges == 0
    x = np.arange(12.0).reshape(6, 2)
    np.testing.assert_array_equal(
        ro.unpermute_vertex_outputs(ro.permute_vertex_features(x)), x)


def test_degree_sort_single_vertex_graph():
    g = graphs.Graph(src=np.zeros(3, np.int32), dst=np.zeros(3, np.int32),
                     n_vertices=1, name="loop")
    for ro in (reorder.degree_sort(g), reorder.identity_order(g)):
        np.testing.assert_array_equal(ro.order, [0])
        np.testing.assert_array_equal(ro.rank, [0])
        assert ro.graph.n_edges == g.n_edges


def test_identity_order_is_identity():
    g = _graph(40, 100, seed=0)
    ro = reorder.identity_order(g)
    assert ro.is_identity and ro.mode == "identity"
    x = np.random.default_rng(0).standard_normal((40, 4))
    np.testing.assert_array_equal(ro.permute_vertex_features(x), x)
    np.testing.assert_array_equal(ro.unpermute_vertex_outputs(x), x)


# ---------------------------------------------------------------------------
# hypothesis: permute ∘ unpermute == id for every reordering
# ---------------------------------------------------------------------------

def test_reorder_round_trip_property():
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property test needs the optional hypothesis dep")
    from hypothesis import given, settings, strategies as st

    graph_st = st.builds(
        lambda v, e, seed, model: graphs.random_graph(v, e, seed=seed,
                                                      model=model),
        v=st.integers(1, 150), e=st.integers(0, 600),
        seed=st.integers(0, 10),
        model=st.sampled_from(["powerlaw", "uniform"]),
    )

    @given(g=graph_st, mode=st.sampled_from(["identity", "in", "out"]),
           f=st.integers(1, 8))
    @settings(max_examples=40, deadline=None)
    def round_trip(g, mode, f):
        ro = (reorder.identity_order(g) if mode == "identity"
              else reorder.degree_sort(g, by=mode))
        x = np.arange(g.n_vertices * f, dtype=np.float32).reshape(-1, f)
        np.testing.assert_array_equal(
            ro.unpermute_vertex_outputs(ro.permute_vertex_features(x)), x)
        # and the permutation really is a bijection
        assert len(set(ro.order.tolist())) == g.n_vertices

    round_trip()


# ---------------------------------------------------------------------------
# cache isolation: layout + reorder provenance in every key
# ---------------------------------------------------------------------------

def test_structure_signature_separates_layouts_and_reorders():
    g = _graph()
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    cs = tiling.csr_tiles(ts)
    sigs = {
        structure_signature("gcn", ts),
        structure_signature("gcn", cs),
        structure_signature("gcn", ts, reorder="degree"),
        structure_signature("gcn", cs, reorder="degree"),
    }
    assert len(sigs) == 4                       # no pair ever aliases
    assert ts.shape_signature()[1] == "coo"
    assert cs.shape_signature()[1] == "csr"


def test_shape_registry_keys_layout_and_reorder_apart():
    g = _graph(100, 400, seed=6)
    reg = ShapeRegistry()
    variants = [("coo", "identity"), ("csr", "identity"),
                ("coo", "degree"), ("csr", "degree")]
    sigs = {}
    for layout, ro_mode in variants:
        key = ("cls", layout, ro_mode)          # engine keys by tuned config
        _, ts, e_rows, ro = reg.canonical(key, g, grid=(4, 4),
                                          reorder=ro_mode, layout=layout)
        assert ro.mode == ro_mode
        assert ts.layout == layout
        sigs[(layout, ro_mode)] = structure_signature(
            "gcn", ts, padded_edges=e_rows, reorder=ro.mode)
    assert len(reg) == len(variants)            # four distinct registrations
    assert len(set(sigs.values())) == len(variants)
    # a second request of each variant lands on the registered shapes —
    # byte-identical signature, i.e. a guaranteed program-cache hit
    for layout, ro_mode in variants:
        _, ts, e_rows, ro = reg.canonical(("cls", layout, ro_mode), g,
                                          grid=(4, 4), reorder=ro_mode,
                                          layout=layout)
        assert structure_signature("gcn", ts, padded_edges=e_rows,
                                   reorder=ro.mode) == sigs[(layout, ro_mode)]


def test_shape_registry_rejects_unknown_reorder():
    reg = ShapeRegistry()
    with pytest.raises(ValueError, match="reorder"):
        reg.canonical("k", _graph(30, 60), reorder="random")


def test_bucketed_csr_tiles_keep_layout_in_signature():
    g = _graph(110, 450, seed=8)
    bt_coo, _ = tiling.build_tiles(g, 4, 4, layout="coo", n_buckets=2)
    bt_csr, _ = tiling.build_tiles(g, 4, 4, layout="csr", n_buckets=2)
    assert bt_coo.shape_signature() != bt_csr.shape_signature()
    assert all(b.layout == "csr" and b.row_ptr is not None
               for b in bt_csr.buckets)
