"""Pallas TPU kernel for ZIPPER tiled SpMM (the paper's core dataflow).

Hardware adaptation (DESIGN.md §2): the ASIC's per-edge gather/scatter units
have no TPU analogue, so a tile's sparse structure is *densified* into an
adjacency block A_t (Dmax × Smax) over the **compacted** sources — sparsity
is exploited structurally (sparse tiling keeps Smax small and drops empty
tiles) while the MXU gets dense work, and the VPU never chases pointers.

Grid = tiles, partition-major.  Scalar-prefetched tile metadata (the "tile
hub"): ``part_id`` drives the output BlockSpec index map (all tiles of one
partition revisit the same output block), ``tile_flags`` marks first/last
tile of each partition for accumulator init/flush.  The Pallas grid pipeline
overlaps tile t+1's A/X DMA with tile t's MXU matmul — the paper's
inter-tile pipelining, realized by the hardware DMA engine.

The backend picks the kernel mode (:func:`interpret_mode`): compiled Mosaic
kernels on a TPU, the Pallas interpreter on the CPU (the test suite), an
error anywhere else.  Every dot runs at ``Precision.HIGHEST`` so f32
operands keep f32 accuracy on the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FIRST, LAST = 1, 2
_HIGHEST = jax.lax.Precision.HIGHEST


def interpret_mode() -> bool:
    """Pallas mode for the default backend: ``False`` (a compiled Mosaic
    kernel) on ``"tpu"``, ``True`` (the interpreter) on ``"cpu"``."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the tile kernels run on 'tpu' (compiled) or 'cpu' "
                       f"(interpreted), not on {backend!r}")


def _kernel(flags_ref, part_ref, a_ref, x_ref, o_ref, acc_ref):
    t = pl.program_id(0)
    flags = flags_ref[t]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0].astype(jnp.float32)          # (D, S)
    x = x_ref[0].astype(jnp.float32)          # (S, F)
    acc_ref[...] += jax.lax.dot(a, x, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def tile_flags(part_id: np.ndarray) -> np.ndarray:
    """FIRST/LAST markers per tile (partition-major tile order)."""
    T = len(part_id)
    f = np.zeros((T,), np.int32)
    for i in range(T):
        if i == 0 or part_id[i] != part_id[i - 1]:
            f[i] |= FIRST
        if i == T - 1 or part_id[i] != part_id[i + 1]:
            f[i] |= LAST
    return f


def tile_spmm_pallas(adj, xsrc, part_id, flags, *, n_parts: int):
    """adj: (T, D, S); xsrc: (T, S, F); part_id/flags: (T,) int32.

    Returns (P, D, F).  Tiles must be partition-major (grid_tile order)."""
    T, D, S = adj.shape
    F = xsrc.shape[-1]
    grid = (T,)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,   # flags, part_id -> SMEM (the tile hub)
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, D, S), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, S, F), lambda t, flags, part: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, D, F), lambda t, flags, part: (part[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((D, F), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, D, F), xsrc.dtype),
        interpret=interpret_mode(),
    )(flags.astype(jnp.int32), part_id.astype(jnp.int32), adj, xsrc)
    return out


# ---------------------------------------------------------------------------
# online-softmax variant (GAT edge softmax in ONE pass over tiles —
# the beyond-paper optimization replacing the 3-phase schedule, §Perf)
# ---------------------------------------------------------------------------

def _softmax_kernel(flags_ref, part_ref, s_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref):
    t = pl.program_id(0)
    flags = flags_ref[t]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    s = s_ref[0].astype(jnp.float32)              # (D, S) masked with <= -1e30
    v = v_ref[0].astype(jnp.float32)              # (S, F)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(s > -1e29, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, precision=_HIGHEST, preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# CSR-within-tile variants (§5.3 / ROADMAP 3): instead of densifying into a
# (D, S) adjacency block, the kernel walks per-tile row pointers.  A row
# selector — sel[d, e] = 1 iff row_ptr[d] <= e < row_ptr[d+1] — reduces the
# per-edge values on the MXU.  Padded edge slots sit at e >= row_ptr[-1]
# where no row's run reaches, so no tail masking is needed; index traffic
# shrinks from 2 int32 per edge (COO pair) to 1 per edge + one (D+1) pointer
# table per tile.
#
# Mosaic layout: the wrapper splits the pointer table into per-row (T, D, 1)
# run bounds and stands per-edge vectors up as (T, E, 1) columns or (T, 1, E)
# rows, so every block's last two dims equal the array's.  The kernel builds
# its selectors from iota comparisons and gathers sources with a one-hot
# matmul — no in-kernel gather, slice or reshape.
# ---------------------------------------------------------------------------

def _csr_row_select(lo, hi, n_cols: int):
    """(D, E) bool selector from (D, 1) run bounds: e in dst row d's run."""
    eidx = jax.lax.broadcasted_iota(jnp.int32, (lo.shape[0], n_cols), 1)
    return (eidx >= lo) & (eidx < hi)


def _row_bounds(row_ptr):
    """(T, D+1) row pointers -> (T, D, 1) run starts and ends."""
    rp = row_ptr.astype(jnp.int32)
    return rp[:, :-1, None], rp[:, 1:, None]


def _csr_kernel(flags_ref, part_ref, lo_ref, hi_ref, col_ref, w_ref, x_ref,
                o_ref, acc_ref):
    t = pl.program_id(0)
    flags = flags_ref[t]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = col_ref[0]                               # (E, 1) local src index
    w = w_ref[0].astype(jnp.float32)               # (E, 1) edge weights
    x = x_ref[0].astype(jnp.float32)               # (S, F)
    E, S = col.shape[0], x.shape[0]
    sidx = jax.lax.broadcasted_iota(jnp.int32, (E, S), 1)
    onehot = jnp.where(sidx == col, w, 0.0)        # (E, S) weighted gather
    gathered = jax.lax.dot(onehot, x, precision=_HIGHEST,
                           preferred_element_type=jnp.float32)   # (E, F)
    sel = _csr_row_select(lo_ref[0], hi_ref[0], E).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(sel, gathered, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def tile_spmm_csr_pallas(row_ptr, col, w, xsrc, part_id, flags, *,
                         n_parts: int):
    """CSR tile SpMM: row_ptr (T, D+1); col/w (T, E); xsrc (T, S, F).

    ``col`` is the tile-local source index per edge (CSR-ordered
    ``edge_src``); ``w`` carries per-edge weights (ones for a pure gather).
    Returns (P, D, F); tiles must be partition-major."""
    T, E = col.shape
    D = row_ptr.shape[1] - 1
    S, F = xsrc.shape[-2:]
    lo, hi = _row_bounds(row_ptr)
    out = pl.pallas_call(
        _csr_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, D, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, D, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, E, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, E, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, S, F), lambda t, flags, part: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, D, F), lambda t, flags, part: (part[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((D, F), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, D, F), xsrc.dtype),
        interpret=interpret_mode(),
    )(flags.astype(jnp.int32), part_id.astype(jnp.int32), lo, hi,
      col.astype(jnp.int32)[:, :, None], w[:, :, None], xsrc)
    return out


def _csr_softmax_kernel(flags_ref, part_ref, lo_ref, hi_ref, s_ref, v_ref,
                        o_ref, acc_ref, m_ref, l_ref):
    t = pl.program_id(0)
    flags = flags_ref[t]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    s_e = s_ref[0].astype(jnp.float32)             # (1, E) per-edge scores
    v = v_ref[0].astype(jnp.float32)               # (E, F) per-edge values
    sel = _csr_row_select(lo_ref[0], hi_ref[0], s_e.shape[1])
    s = jnp.where(sel, s_e, -1e30)                 # (D, E)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(sel, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, precision=_HIGHEST, preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def segment_softmax_csr_pallas(row_ptr, scores, vals, part_id, flags, *,
                               n_parts: int):
    """CSR single-pass segment softmax: row_ptr (T, D+1); scores (T, E);
    vals (T, E, F) per-edge source values (already gathered)."""
    T, E = scores.shape
    D = row_ptr.shape[1] - 1
    F = vals.shape[-1]
    lo, hi = _row_bounds(row_ptr)
    out = pl.pallas_call(
        _csr_softmax_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, D, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, D, 1), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, 1, E), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, E, F), lambda t, flags, part: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, D, F), lambda t, flags, part: (part[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((D, F), jnp.float32),
                            pltpu.VMEM((D, 1), jnp.float32),
                            pltpu.VMEM((D, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, D, F), vals.dtype),
        interpret=interpret_mode(),
    )(flags.astype(jnp.int32), part_id.astype(jnp.int32), lo, hi,
      scores[:, None, :], vals)
    return out


def segment_softmax_pallas(scores, vals, part_id, flags, *, n_parts: int):
    """Single-pass segment softmax over partition tiles (flash-style)."""
    T, D, S = scores.shape
    F = vals.shape[-1]
    out = pl.pallas_call(
        _softmax_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, D, S), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, S, F), lambda t, flags, part: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, D, F), lambda t, flags, part: (part[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((D, F), jnp.float32),
                            pltpu.VMEM((D, 1), jnp.float32),
                            pltpu.VMEM((D, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, D, F), vals.dtype),
        interpret=interpret_mode(),
    )(flags.astype(jnp.int32), part_id.astype(jnp.int32), scores, vals)
    return out
