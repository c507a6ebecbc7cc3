"""R-GCN's typed aggregation around the relation kernel:

    out_i = sum over typed edges e into i of
            scale_e * (h_src(e) @ blockdiag(W[rel(e)]))

over a relation-grouped layout (``tiling.relation_layout``).  Each part runs
under the ``stage`` context the caller passes (the runner's named scopes):

* ``vertex``: the source features put in the kernel form's column layout,
  and the aggregated rows put back;
* ``edge``: each row's source features gathered and scaled by its edge
  weight (zero on padded rows), and the messages gathered into
  destination order;
* ``kernel``: the weights' layout, the transform kernel, and the kernel
  that sums the messages into their destinations.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from ..tile_spmm.kernel import tile_flags
from .kernel import relation_sum_pallas, relation_transform_pallas

LANE = 128
#: largest block edge the ``lanes`` form takes (k * m VPU multiply-adds per
#: output lane); larger blocks go to the MXU as a dense matrix
LANES_MAX_BLOCK = 8


def form_of(w_shape) -> str:
    """The kernel form for weights (n_types, n_blocks, k, m)."""
    _, _, k, m = w_shape
    return "lanes" if max(k, m) <= LANES_MAX_BLOCK else "dense"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def to_rows(h, w_shape):
    """(V, n_blocks * k) features in the form's column layout."""
    _, nb, k, _ = w_shape
    if form_of(w_shape) == "dense":
        return jnp.pad(h, ((0, 0), (0, _round_up(h.shape[1], LANE)
                                    - h.shape[1])))
    x = h.reshape(h.shape[0], nb, k).transpose(0, 2, 1)    # (V, k, nb)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, _round_up(nb, LANE) - nb)))
    return x.reshape(h.shape[0], -1)


def from_rows(y, w_shape):
    """The inverse of :func:`to_rows` on output rows: (V, n_blocks * m)."""
    _, nb, _, m = w_shape
    if form_of(w_shape) == "dense":
        return y[:, :nb * m]
    y = y.reshape(y.shape[0], m, -1)[:, :, :nb]             # (V, m, nb)
    return y.transpose(0, 2, 1).reshape(y.shape[0], nb * m)


def kernel_weights(w):
    """``lanes``: (R, k * m, Bp), row ``i * m + o`` = ``w[r, :, i, o]``;
    ``dense``: the block-diagonal (R, Fi, Fo), padded to 128 lanes."""
    r, nb, k, m = w.shape
    if form_of(w.shape) == "dense":
        eye = jnp.eye(nb, dtype=w.dtype)
        wd = (w[:, :, :, None, :] * eye[None, :, None, :, None]).reshape(
            r, nb * k, nb * m)
        return jnp.pad(wd, ((0, 0), (0, _round_up(nb * k, LANE) - nb * k),
                            (0, _round_up(nb * m, LANE) - nb * m)))
    wl = w.transpose(0, 2, 3, 1).reshape(r, k * m, nb)
    return jnp.pad(wl, ((0, 0), (0, 0), (0, _round_up(nb, LANE) - nb)))


def layout_operands(lay) -> dict:
    """Device arrays of a ``tiling.RelationLayout`` and its destination
    tiles' FIRST/LAST flags: the ``rel`` operand of the runners."""
    ops = {k: jnp.asarray(getattr(lay, k)) for k in
           ("slot_src", "slot_dst", "slot_gid", "block_rel", "sum_src",
            "sum_dst", "sum_part")}
    ops["sum_flags"] = jnp.asarray(tile_flags(lay.sum_part))
    return ops


def _no_stage(name: str):
    return contextlib.nullcontext()


def relation_aggregate(h, scale, w, rel, *, n_out: int, stage=_no_stage):
    """h: (V, Fi) source values; scale: (n_slots,) per-row edge weight, 0
    on padded rows; w: (R, n_blocks, k, m); ``rel``: a
    ``tiling.RelationLayout``'s arrays (``slot_src``, ``block_rel``,
    ``sum_src``, ``sum_dst``, ``sum_part``) and ``sum_flags``, the
    destination tiles' FIRST/LAST flags.  Returns (n_out, n_blocks * m)."""
    _, _, k, m = w.shape
    n_tiles, _, rows_per = rel["sum_dst"].shape
    block_rows = rel["slot_src"].shape[0] // rel["block_rel"].shape[0]
    with stage("vertex"):
        rows = to_rows(h, w.shape)
    with stage("edge"):
        xs = rows[rel["slot_src"]] * scale[:, None]
    with stage("kernel"):
        msg = relation_transform_pallas(xs, kernel_weights(w),
                                        rel["block_rel"],
                                        block_rows=block_rows,
                                        form=form_of(w.shape), k=k, m=m)
    with stage("edge"):
        by_dst = msg[rel["sum_src"]].reshape(n_tiles, rows_per, -1)
    with stage("kernel"):
        n_parts = -(-n_out // rows_per)
        agg = relation_sum_pallas(rel["sum_dst"], by_dst, rel["sum_part"],
                                  rel["sum_flags"], n_parts=n_parts,
                                  part_rows=rows_per)
    with stage("vertex"):
        return from_rows(agg.reshape(n_parts * rows_per, -1)[:n_out],
                         w.shape)
