"""Compile the four tile kernels for a described TPU v5e chip, no chip needed.

Each case compiles one of the COO/CSR SpMM and segment-softmax kernels at
F=128 for one chip of a ``v5e:2x2`` topology and asserts that the program
holds a Mosaic kernel (``tpu_custom_call``).  Two kinds of shapes:

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
KERNELS = ("spmm", "segment_softmax", "spmm_csr", "segment_softmax_csr")


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
