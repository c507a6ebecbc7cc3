"""Pure-jnp oracle for tiled SpMM (gather -> aggregate per destination).

Given ZIPPER tiles in block-dense form — per tile a dense adjacency block
A (Dmax, Smax) over the compacted sources and the gathered source features
X (Smax, F) — the reference accumulates  out[p] = sum_{tiles t of p} A_t X_t.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def tile_spmm_ref(adj, xsrc, part_id, n_parts: int):
    """adj: (T, D, S); xsrc: (T, S, F); part_id: (T,) -> out (P, D, F)."""
    T, D, S = adj.shape
    F = xsrc.shape[-1]
    out = jnp.zeros((n_parts, D, F), jnp.float32)
    contrib = jnp.einsum("tds,tsf->tdf", adj.astype(jnp.float32),
                         xsrc.astype(jnp.float32), precision=_HIGHEST)
    return out.at[part_id].add(contrib)


def _csr_select(row_ptr, n_edge_cols: int):
    """(T, D, E) selector from (T, D+1) row pointers: 1 iff edge e in row d."""
    e = jnp.arange(n_edge_cols)[None, None, :]
    lo = row_ptr[:, :-1, None]
    hi = row_ptr[:, 1:, None]
    return (e >= lo) & (e < hi)


def tile_spmm_csr_ref(row_ptr, col, w, xsrc, part_id, n_parts: int):
    """CSR oracle: row_ptr (T, D+1); col/w (T, E); xsrc (T, S, F)."""
    T, E = col.shape
    F = xsrc.shape[-1]
    D = row_ptr.shape[1] - 1
    gathered = w[..., None].astype(jnp.float32) * \
        jnp.take_along_axis(xsrc.astype(jnp.float32), col[..., None], axis=1)
    sel = _csr_select(row_ptr, E).astype(jnp.float32)       # (T, D, E)
    contrib = jnp.einsum("tde,tef->tdf", sel, gathered, precision=_HIGHEST)
    return jnp.zeros((n_parts, D, F), jnp.float32).at[part_id].add(contrib)


def segment_softmax_csr_ref(row_ptr, scores, vals, part_id, n_parts: int):
    """CSR softmax oracle: scores (T, E) per edge; vals (T, E, F) per edge."""
    T, E = scores.shape
    F = vals.shape[-1]
    D = row_ptr.shape[1] - 1
    sel = _csr_select(row_ptr, E)                           # (T, D, E)
    s = jnp.where(sel, scores.astype(jnp.float32)[:, None, :], -1e30)
    neg = -1e30
    m = jnp.full((n_parts, D), neg).at[part_id].max(s.max(-1))
    m = jnp.maximum(m, neg)
    p = jnp.exp(s - m[part_id][..., None])
    p = jnp.where(sel, p, 0.0)
    l = jnp.zeros((n_parts, D)).at[part_id].add(p.sum(-1))
    acc = jnp.zeros((n_parts, D, F)).at[part_id].add(
        jnp.einsum("tde,tef->tdf", p, vals.astype(jnp.float32),
                   precision=_HIGHEST))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def segment_softmax_ref(scores, vals, part_id, n_parts: int):
    """Online-softmax aggregation oracle.

    scores: (T, D, S) masked with -inf where no edge; vals: (T, S, F).
    out[p, d] = sum_e softmax(scores over all tiles of p at row d) * vals.
    """
    T, D, S = scores.shape
    F = vals.shape[-1]
    s = scores.astype(jnp.float32)
    # global per-(partition,row) max and sum across that partition's tiles
    neg = -1e30
    m = jnp.full((n_parts, D), neg).at[part_id].max(s.max(-1))
    m = jnp.maximum(m, neg)
    p = jnp.exp(s - m[part_id][..., None])
    p = jnp.where(s > neg / 2, p, 0.0)
    l = jnp.zeros((n_parts, D)).at[part_id].add(p.sum(-1))
    acc = jnp.zeros((n_parts, D, F)).at[part_id].add(
        jnp.einsum("tds,tsf->tdf", p, vals.astype(jnp.float32),
                   precision=_HIGHEST))
    return acc / jnp.maximum(l, 1e-30)[..., None]
