"""Work counts of the benchmark (bench/configs/<model>.py) against hand
counts: they come from V, E, F and the layer equations alone."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.manifest import Manifest  # noqa: E402

MAN = Manifest()


def cfg(name, **kw):
    c = MAN.config(name)
    c.update(kw)
    return c


def test_gcn_counts_by_hand():
    # one layer, F 2 -> 3, on V=4 vertices and E=5 edges
    c = cfg("gcn2-e128", layers=1, in_dim=2, hidden_dim=3, out_dim=3)
    m = MAN.model("gcn")
    # transform 2*4*2*3=48, edge norm 5, messages+sum 2*5*3=30, relu 4*3=12
    assert m.model_flops(c, 4, 5) == 48 + 5 + 30 + 12
    # kernel: 2*5*3 flops; bytes 2*4*3*4 rows + 5*8 index+weight + 5*4 ptrs
    assert m.kernel_work(c, 4, 5) == (30.0, 96 + 40 + 20)


def test_gat_counts_by_hand():
    c = cfg("gat2-e128", layers=1, in_dim=2, hidden_dim=3, out_dim=3)
    m = MAN.model("gat")
    # transform 48, score mat-vecs 4*4*3=48, per-edge 7*5=35, messages 30
    assert m.model_flops(c, 4, 5) == 48 + 48 + 35 + 30
    # kernel: 4*5 softmax + 2*5*3 weighted sum + 4*3 divides
    assert m.kernel_work(c, 4, 5) == (20 + 30 + 12.0, 96 + 40 + 20)


@pytest.mark.parametrize("name,flops,kernel", [
    # the full cells' graph (45,293 V, 217,098 directed E), two layers of
    # 128 -> 128
    ("gcn2-e128", 2 * (2 * 45293 * 128 * 128 + 217098 + 2 * 217098 * 128
                       + 45293 * 128),
     (2 * 2 * 217098 * 128.0,
      2 * (2 * 45293 * 128 * 4 + 217098 * 8 + 45294 * 4))),
    ("gat2-e128", 2 * (2 * 45293 * 128 * 128 + 4 * 45293 * 128
                       + 7 * 217098 + 2 * 217098 * 128),
     (2 * (4 * 217098 + 2 * 217098 * 128 + 45293 * 128.0),
      2 * (2 * 45293 * 128 * 4 + 217098 * 8 + 45294 * 4))),
])
def test_counts_at_full_cell_size(name, flops, kernel):
    c = MAN.config(name)
    m = MAN.model(c["model"])
    assert m.model_flops(c, 45293, 217098) == flops
    assert m.kernel_work(c, 45293, 217098) == kernel
    # about 97 MB of least bytes per forward: the bytes bound the kernel
    assert 90e6 < kernel[1] < 100e6
    assert kernel[0] / 197e12 < kernel[1] / 819e9


@pytest.mark.parametrize("name", ["gcn2-e128", "gat2-e128"])
def test_counts_take_no_tiled_shape(name):
    """The counts' only inputs are the configuration and V, E: no tile
    set, grid or padding can reach them."""
    import inspect
    m = MAN.model(MAN.config(name)["model"])
    for fn in (m.model_flops, m.kernel_work):
        assert list(inspect.signature(fn).parameters) == [
            "cfg", "n_vertices", "n_edges"]
