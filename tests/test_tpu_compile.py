"""Compile the four tile kernels and the relation kernel for a described TPU
v5e chip, no chip needed.

Each tile case compiles one of the COO/CSR SpMM and segment-softmax kernels
at F=128 for one chip of a ``v5e:2x2`` topology and asserts that the program
holds a Mosaic kernel (``tpu_custom_call``).  The COO segment softmax
compiles in both forms the runner calls: one column per edge slot and one
per source on the tile grid (``segment_softmax_grid``).  The relation kernel
compiles at the R-GCN cell's shape and as one dense matrix.  The runner's
whole forward compiles too, for gcn and gat on tiny COO tiles, to check that
it scatters nothing over the tile grid (the edge count is bound at ``bind``)
and that the grid select keeps its ``zipper.densify`` op name.  Two
kinds of tile shapes for the kernels:

* ``serving`` — a 16-graph request of ~64 V / 256 E graphs, canonicalized
  by :class:`~repro.serve.signature.ShapeRegistry` (``target_part=256``);
* ``full_graph`` — ak2010 at its published size on the grid
  ``chip_smoke.py`` runs (``FULL_GRID``).

The TPU compiler refuses here what it would refuse on the chip: blocks that
break Mosaic's tiling rules, or more VMEM than a kernel may use.  The
topology is described inside a module fixture, never at import, so every
pytest-xdist worker collects the same tests and only the one running this
file loads the TPU library.
"""
import functools
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tiling
from repro.gnn import graphs
from repro.kernels.tile_spmm import kernel as K
from repro.serve.signature import ShapeRegistry

ROOT = pathlib.Path(__file__).resolve().parents[1]
F = 128
KERNELS = ("spmm", "segment_softmax", "spmm_csr", "segment_softmax_csr",
           "segment_softmax_grid")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernels to compiled Mosaic (the default backend here is the
    CPU) and keep the persistent compilation cache out of it: a described
    chip's programs are written but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(K, "interpret_mode", lambda: False)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _serving_tiles(layout):
    gs = [graphs.random_graph(64, 256, seed=k, model="powerlaw")
          for k in range(16)]
    batch = graphs.batch_graphs(gs)
    _, ts, _, _ = ShapeRegistry(target_part=256).canonical(
        "serving", batch.graph, layout=layout)
    return ts


def _full_graph_tiles(layout):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import chip_smoke
    g = graphs.paper_graph(chip_smoke.GRAPH, chip_smoke.SCALE,
                           seed=chip_smoke.SEED)
    ts, _ = tiling.build_tiles(g, chip_smoke.FULL_GRID, chip_smoke.FULL_GRID,
                               layout=layout)
    return ts


def _kernel_args(kernel, ts, sharding):
    """(kernel fn, operand shapes) as the runners call it on ``ts``."""
    T, D, S, E = ts.n_tiles, int(ts.part_size.max()), ts.s_max, ts.e_max

    def a(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    meta = (a((T,), jnp.int32), a((T,), jnp.int32))          # part_id, flags
    if kernel == "spmm":
        return K.tile_spmm_pallas, (a((T, D, S)), a((T, S, F))) + meta
    if kernel == "segment_softmax":
        return K.segment_softmax_pallas, (a((T, D, E)), a((T, E, F))) + meta
    if kernel == "segment_softmax_grid":
        return K.segment_softmax_pallas, (a((T, D, S)), a((T, S, F))) + meta
    rp = a((T, D + 1), jnp.int32)
    if kernel == "spmm_csr":
        return K.tile_spmm_csr_pallas, (rp, a((T, E), jnp.int32), a((T, E)),
                                        a((T, S, F))) + meta
    return K.segment_softmax_csr_pallas, (rp, a((T, E)), a((T, E, F))) + meta


@pytest.mark.parametrize("shapes", ["serving", "full_graph"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, shapes, one_chip, mosaic):
    layout = "csr" if kernel.endswith("_csr") else "coo"
    ts = (_serving_tiles if shapes == "serving" else _full_graph_tiles)(layout)
    assert ts.layout == layout
    fn, args = _kernel_args(kernel, ts, one_chip)
    compiled = jax.jit(functools.partial(fn, n_parts=ts.n_dst_parts)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (ts.n_dst_parts, int(ts.part_size.max()), F)
    assert np.dtype(out.dtype) == np.float32


@pytest.mark.parametrize("form", ["lanes", "dense"])
def test_relation_kernel_compiles_for_v5e(form, one_chip, mosaic):
    """R-GCN's relation kernel: ``lanes`` at the FB15k-237 cell's shape (474
    relations of 100 5x5 blocks, 640 lanes, 4,725 blocks of 128 rows),
    ``dense`` for one full 128x128 matrix per relation (3 relations)."""
    from repro.kernels.relation import kernel as RK
    from repro.kernels.relation import ops as RO

    rows = 128
    if form == "lanes":
        w_shape, n_blocks = (474, 100, 5, 5), 4725
    else:
        w_shape, n_blocks = (3, 1, 128, 128), 64
    _, nb, k, m = w_shape
    assert RO.form_of(w_shape) == form
    w = jax.eval_shape(RO.kernel_weights,
                       jax.ShapeDtypeStruct(w_shape, jnp.float32))
    x = jax.eval_shape(functools.partial(RO.to_rows, w_shape=w_shape),
                       jax.ShapeDtypeStruct((n_blocks * rows, nb * k),
                                            jnp.float32))

    def a(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    fn = functools.partial(RK.relation_transform_pallas, block_rows=rows,
                           form=form, k=k, m=m)
    compiled = jax.jit(fn).lower(
        a(x), a(w), a(jax.ShapeDtypeStruct((n_blocks,), jnp.int32))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{RK.NAME}" in text
    # square blocks: the output rows are as wide as the input rows
    assert compiled.out_info.shape == x.shape


def test_relation_sum_kernel_compiles_for_v5e(one_chip, mosaic):
    """The destination-sum kernel at the R-GCN cell's shape: 4,839 tiles of
    128 message rows of 640 lanes into 114 partitions of 128 rows."""
    from repro.kernels.relation import kernel as RK

    T, S, W, P = 4839, 128, 640, 114

    def a(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = functools.partial(RK.relation_sum_pallas, n_parts=P, part_rows=S)
    compiled = jax.jit(fn).lower(a((T, 1, S), jnp.int32), a((T, S, W)),
                                 a((T,), jnp.int32),
                                 a((T,), jnp.int32)).compile()
    assert f"%{RK.SUM_NAME}" in compiled.as_text()
    assert compiled.out_info.shape == (P, S, W)


def _elements(ln: str):
    """Element count of an f32 HLO instruction's result (``None``: not one)."""
    m = re.search(r"= f32\[([\d,]*)\]", ln)
    return None if m is None else int(np.prod(
        [int(d) for d in m.group(1).split(",") if d]))


@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_forward_reads_the_bound_count_on_v5e(model, one_chip, mosaic):
    """The tile grid's edge count is bound at ``bind``: the forward compiled
    for the chip scatters nothing over the (T, Dmax, Smax) grid, and the
    fusions holding the grid select that reads the count keep the
    ``zipper.densify`` op name the device trace attributes stages by."""
    from repro.core import compiler, pipeline
    from repro.gnn import models

    g = graphs.random_graph(300, 1500, seed=1, model="powerlaw")
    ts, _ = tiling.build_tiles(g, 4, 4)
    tr = models.trace_stacked(model, 2, 16, 16, 16)
    runner = pipeline.PipelinedRunner(compiler.compile_gnn(tr), g, ts,
                                      kernel_dispatch=True)
    assert runner.weight_paths["grid"] + runner.score_paths["grid"] == 2
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        runner._args(models.init_inputs(tr, g, 1),
                     models.init_params(tr, 1), None))
    assert runner.bound_counts == 1
    lines = runner._jitted.lower(*args).compile().as_text().splitlines()

    cells = ts.n_tiles * int(ts.part_size.max()) * ts.s_max
    assert not [ln for ln in lines
                if " scatter(" in ln and _elements(ln) == cells]
    comp, holder = None, set()        # fused computations of the grid select
    for ln in lines:
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{"):
            comp = ln.split()[0].lstrip("%")
        elif (" select(" in ln and _elements(ln) == cells
              and "zipper.densify/" in ln):
            holder.add(comp)
    assert holder
    for name in holder:
        calls = [ln for ln in lines
                 if re.search(rf"calls=%?{re.escape(name)}\b", ln)]
        assert calls and all('op_name="jit(_run)/zipper.densify/' in ln
                             for ln in calls)
