"""Expert-parallel MoE layer (DeepSeek V2/V3 family).

ZIPPER's full pipeline applied to MoE (DESIGN.md §4): token->expert routes
are the sparse graph; routing sorts tokens by expert (degree-sort reorder),
capacity buckets are the tiles, dead bucket blocks are skipped structurally
(Pallas kernel), and token *chunking* scans tiles through the layer to bound
the transient dispatch footprint — inter-tile pipelining along the token
axis.

Distribution (under ``jax.shard_map``):
  * experts sharded over the **data** axis (E_loc = E / n_data per device),
  * expert FFN width sharded over the **model** axis (f_loc = f / n_model),
  * dispatch/return via ``all_to_all`` over data; down-proj reduced by
    ``psum`` over model;
  * shared experts are a dense SwiGLU, TP over model.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..kernels.moe_dispatch import ops as moe_ops
from .common import DP, leaf

Array = Any


def moe_template(cfg: ArchConfig) -> Dict:
    mo = cfg.moe
    d, f = cfg.d_model, mo.d_ff_expert
    t = {
        "router": leaf((d, mo.n_routed), (None, None), dtype="float32"),
        # experts: E over the data axes (expert parallel), ff over model (TP)
        "wg": leaf((mo.n_routed, d, f), (DP, None, "model")),
        "wu": leaf((mo.n_routed, d, f), (DP, None, "model")),
        "wd": leaf((mo.n_routed, f, d), (DP, "model", None)),
    }
    if mo.aux_free_bias:
        t["router_bias"] = leaf((mo.n_routed,), (None,), init="zeros", dtype="float32")
    if mo.n_shared:
        fs = mo.d_ff_expert * mo.n_shared
        t["shared_wg"] = leaf((d, fs), (None, "model"))
        t["shared_wu"] = leaf((d, fs), (None, "model"))
        t["shared_wd"] = leaf((fs, d), ("model", None))
    return t


def _local_moe(cfg: ArchConfig, x_loc: Array, router, router_bias, wg, wu, wd,
               *, n_data: int, capacity: int, axis_data: Tuple[str, ...],
               axis_model: str):
    """Per-device body (inside shard_map).

    x_loc: (T_loc, d); wg/wu: (E_loc, d, f_loc); wd: (E_loc, f_loc, d)."""
    mo = cfg.moe
    E = mo.n_routed
    E_loc = E // n_data
    r = moe_ops.route(x_loc, router.astype(x_loc.dtype), mo.top_k, capacity,
                      norm_topk=mo.norm_topk, router_bias=router_bias)
    buckets = moe_ops.dispatch(x_loc, r, E, capacity)          # (E, C, d)
    d = x_loc.shape[-1]
    # ---- expert-parallel all_to_all over the data axis ----------------------
    from .. import runtime_flags
    fp8 = runtime_flags.OPT["moe_fp8_dispatch"]
    b = buckets.reshape(n_data, E_loc, capacity, d)
    if fp8:
        # §Perf: halve the forward dispatch wire bytes (per-chunk scale kept
        # bf16; gradients flow through the straight-through cast in bf16)
        bscale = jnp.maximum(jnp.max(jnp.abs(b)), 1e-6) / 448.0
        b = (b / bscale).astype(jnp.float8_e4m3fn)
    if n_data > 1:
        b = jax.lax.all_to_all(b, axis_data, split_axis=0, concat_axis=0, tiled=False)
    if fp8:
        b = b.astype(x_loc.dtype) * bscale
    # b[j] now holds source-shard j's buckets for MY experts
    b = b.transpose(1, 0, 2, 3).reshape(E_loc, n_data * capacity, d)
    # ---- grouped FFN over local experts (ff sharded over model) -------------
    h = jnp.einsum("ecd,edf->ecf", b, wg)
    u = jnp.einsum("ecd,edf->ecf", b, wu)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h.astype(jnp.float32)).astype(h.dtype) * u,
                   wd)
    from .. import runtime_flags
    rs_mode = runtime_flags.OPT["moe_rs_combine"]
    n_model = jax.lax.psum(1, axis_model)
    if rs_mode and d % n_model == 0:
        # §Perf: reduce-scatter (half an all-reduce) and carry only d/n_model
        # through the return all_to_all; re-assemble tokens with one thin
        # all-gather at the end.
        y = jax.lax.psum_scatter(y, axis_model, scatter_dimension=2, tiled=True)
        d_s = d // n_model
    else:
        y = jax.lax.psum(y, axis_model)
        d_s = d
    # ---- return path ---------------------------------------------------------
    y = y.reshape(E_loc, n_data, capacity, d_s).transpose(1, 0, 2, 3)
    if n_data > 1:
        y = jax.lax.all_to_all(y, axis_data, split_axis=0, concat_axis=0, tiled=False)
    y = y.reshape(E, capacity, d_s)
    out = moe_ops.combine(y, r, x_loc.shape[0])          # (T_loc, d_s)
    if rs_mode and d_s != d:
        out = jax.lax.all_gather(out, axis_model, axis=1, tiled=True)  # (T_loc, d)
    aux = r.aux_loss
    if n_data > 1:
        aux = jax.lax.pmean(aux, axis_data)
    return out, aux


def moe_layer(cfg: ArchConfig, p: Dict, x: Array, *, mesh,
              token_chunks: int = 4) -> Tuple[Array, Array]:
    """x: (B, S, d) -> (y, aux_loss).  Requires a mesh with a 'model' axis;
    the data axes carry both tokens and experts."""
    mo = cfg.moe
    B, S, d = x.shape
    axis_data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in axis_data:
        n_data *= mesh.shape[a]
    assert mo.n_routed % n_data == 0, (mo.n_routed, n_data)
    dp_spec = axis_data if len(axis_data) > 1 else axis_data[0]
    has_bias = "router_bias" in p

    def body(x_blk):  # (n_data * T_loc, d) global-view chunk
        T_loc = x_blk.shape[0] // n_data
        cap = max(8, int(T_loc * mo.top_k / mo.n_routed * mo.capacity_factor))

        def device_fn(xd, router, router_bias, wg, wu, wd):
            return _local_moe(cfg, xd.reshape(T_loc, d), router, router_bias,
                              wg, wu, wd, n_data=n_data, capacity=cap,
                              axis_data=axis_data, axis_model="model")

        fn = jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(P(dp_spec, None),               # tokens over data axes
                      P(None, None),                  # router (replicated)
                      P(None) if has_bias else P(),   # balancing bias
                      P(dp_spec, None, "model"),      # experts: E over data, f over model
                      P(dp_spec, None, "model"),
                      P(dp_spec, "model", None)),
            out_specs=(P(dp_spec, None), P()),
            check_vma=False)
        y, aux = fn(x_blk, p["router"],
                    p["router_bias"] if has_bias else jnp.zeros((), x.dtype),
                    p["wg"], p["wu"], p["wd"])
        return y, aux

    flat = x.reshape(B * S, d)
    from .. import runtime_flags
    if runtime_flags.probe_stacks() is not None:
        token_chunks = 1  # cost probe: all tokens through one dispatch
    if token_chunks > 1 and (B * S) % (token_chunks * n_data) == 0:
        chunks = flat.reshape(token_chunks, (B * S) // token_chunks, d)
        ys, auxs = jax.lax.map(body, chunks)
        y = ys.reshape(B * S, d)
        aux = auxs.mean()
    else:
        y, aux = body(flat)
    y = y.reshape(B, S, d)

    if mo.n_shared:
        h = jax.nn.silu((x @ p["shared_wg"]).astype(jnp.float32)).astype(x.dtype)
        y = y + (h * (x @ p["shared_wu"])) @ p["shared_wd"]
    return y, aux


def dense_ffn_template(cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": leaf((d, f), (None, "model")),
        "wu": leaf((d, f), (None, "model")),
        "wd": leaf((f, d), ("model", None)),
    }


def dense_ffn(p: Dict, x: Array) -> Array:
    h = jax.nn.silu((x @ p["wg"]).astype(jnp.float32)).astype(x.dtype)
    return (h * (x @ p["wu"])) @ p["wd"]


def gelu_ffn_template(cfg: ArchConfig) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    return {"w1": leaf((d, f), (None, "model")),
            "b1": leaf((f,), ("model",), init="zeros"),
            "w2": leaf((f, d), ("model", None)),
            "b2": leaf((d,), (None,), init="zeros")}


def gelu_ffn(p: Dict, x: Array) -> Array:
    h = jax.nn.gelu((x @ p["w1"] + p["b1"]).astype(jnp.float32)).astype(x.dtype)
    return h @ p["w2"] + p["b2"]
