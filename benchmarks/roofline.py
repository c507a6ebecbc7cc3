"""§Roofline: three-term roofline per (arch × shape) from the dry-run.

Reads ``reports/dryrun/single/*.json`` (written by repro.launch.dryrun) and
derives, per cell (all quantities PER DEVICE, from the SPMD module):

  compute_s    = probe FLOPs / 197e12             (bf16 peak per v5e chip)
  memory_s     = modeled HBM bytes / 819e9        (see below)
  collective_s = probe collective bytes / 50e9    (per-chip ICI link class)

FLOPs and collective bytes come from the unrolled cost probes (XLA's
cost_analysis does not scale while-loop bodies — launch/dryrun._probe_costs).

Memory term: the CPU backend's "bytes accessed" counts every unfused HLO
op's operands — on TPU, XLA fuses elementwise chains, so that number
overstates HBM traffic by ~an order of magnitude.  We therefore report BOTH:
``hlo_bytes`` (the raw compiled-artifact number, an upper bound) and a
fusion-modeled estimate used for the roofline terms:

  train:   3×params (fwd + remat + bwd reads) + param write + 4-byte grads
           r/w + opt-state r/w + C_act·tokens·d·L activation round-trips
  prefill: params + C_act·tokens·d·L + KV-cache write
  decode:  params + KV/state-cache read (from memory_analysis arg bytes)

MODEL_FLOPS includes the attention term (6·N·D alone under-credits
long-context cells): train 6·N_act·T + 6·T·S·H·Dh·L; prefill 2·N_act·T +
2·T·S·H·Dh·L (causal half); decode 2·N_act·B + 4·B·S·H·Dh·L.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

from repro.configs import SHAPES, all_configs
from repro.launch.steps import opt_state_bits

from .common import REPORTS, fmt_table, write_report

PEAK_FLOPS = 197e12   # bf16 / chip
HBM_BW = 819e9        # B/s / chip
ICI_BW = 50e9         # B/s / link
C_ACT_TRAIN = 20      # activation round-trips per layer, fwd+bwd, post-fusion
C_ACT_FWD = 6


def _attn_dims(cfg):
    if cfg.family in ("ssm",):
        return 0, 0, 0
    L = cfg.n_layers if cfg.family != "hybrid" else cfg.n_layers // cfg.shared_attn_every
    if cfg.family == "moe":
        H, Dh = cfg.n_heads, cfg.mla.qk_nope + cfg.mla.qk_rope
    else:
        H, Dh = cfg.n_heads, cfg.hdim
    return L, H, Dh


def model_flops_per_device(cfg, shape_name: str, n_devices: int) -> float:
    S, B, kind = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    L, H, Dh = _attn_dims(cfg)
    S_eff = min(S, cfg.attn_window) if cfg.attn_window else S
    if kind == "train":
        T = S * B
        return (6.0 * n_active * T + 6.0 * T * S_eff * H * Dh * L / 2) / n_devices
    if kind == "prefill":
        T = S * B
        return (2.0 * n_active * T + 2.0 * T * S_eff * H * Dh * L / 2) / n_devices
    return (2.0 * n_active * B + 4.0 * B * S_eff * H * Dh * L) / n_devices


def modeled_hbm_bytes(cfg, cell: Dict) -> float:
    """Fusion-modeled per-device HBM traffic per step (see module doc)."""
    S, B, kind = SHAPES[cell["shape"]]
    n_dev = cell["n_devices"]
    P = cfg.param_count()
    p_dev = P * 2 / n_dev                      # bf16 params resident/device
    tokens_dev = S * B / n_dev
    d, L = cfg.d_model, cfg.n_layers
    args = cell["memory"].get("argument_size_in_bytes", 0)
    if kind == "train":
        bits = opt_state_bits(cfg)
        opt_dev = P * (3.1 if bits == 8 else 8.0) / n_dev
        grads = P * 4 / n_dev
        act = C_ACT_TRAIN * tokens_dev * d * L * 2
        return 4 * p_dev + 2 * grads + 2 * opt_dev + act
    if kind == "prefill":
        act = C_ACT_FWD * tokens_dev * d * L * 2
        return p_dev + act
    # decode: weights + the cache (arg bytes minus params ~= cache+state)
    cache_dev = max(args - p_dev, 0)
    return p_dev + cache_dev


def load_cells(mesh: str = "single") -> List[Dict]:
    cells = []
    d = pathlib.Path(REPORTS) / "dryrun" / mesh
    for f in sorted(d.glob("*.json")):
        cells.append(json.loads(f.read_text()))
    return cells


def analyze(cells: Optional[List[Dict]] = None, mesh: str = "single") -> List[Dict]:
    cells = cells if cells is not None else load_cells(mesh)
    cfgs = all_configs()
    out = []
    for c in cells:
        if c.get("status") != "ok":
            out.append({"arch": c["arch"], "shape": c["shape"],
                        "status": c.get("status"),
                        "reason": c.get("reason", c.get("error", ""))[:90]})
            continue
        cfg = cfgs[c["arch"]]
        probe = c.get("probe", {}).get("totals", {})
        flops = probe.get("flops", c["flops"])
        hlo_bytes = probe.get("bytes", c.get("hlo_bytes_accessed", 0))
        coll = sum(v for k, v in probe.items() if k.startswith("coll_")) if probe \
            else sum(c["collective_bytes"].values())
        mdl_bytes = modeled_hbm_bytes(cfg, c)
        t_c = flops / PEAK_FLOPS
        t_m = mdl_bytes / HBM_BW
        t_x = coll / ICI_BW
        terms = {"compute": t_c, "memory": t_m, "collective": t_x}
        dom = max(terms, key=terms.get)
        step_s = max(terms.values())  # perfectly-overlapped lower bound
        mf = model_flops_per_device(cfg, c["shape"], c["n_devices"])
        mfu = mf / PEAK_FLOPS / step_s if step_s > 0 else 0.0
        out.append({
            "arch": c["arch"], "shape": c["shape"], "status": "ok",
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "hlo_bytes_s": hlo_bytes / HBM_BW,
            "dominant": dom, "model_flops": mf, "hlo_flops": flops,
            "useful_ratio": mf / flops if flops else 0.0,
            "roofline_frac": mfu,
            "mem_temp_gb": c["memory"].get("temp_size_in_bytes", 0) / 2**30,
            "mem_args_gb": c["memory"].get("argument_size_in_bytes", 0) / 2**30,
        })
    return out


def run(quick: bool = False, mesh: str = "single"):
    rows = analyze(mesh=mesh)
    table = []
    for r in rows:
        if r.get("status") != "ok":
            table.append([r["arch"], r["shape"], r.get("status"),
                          "-", "-", "-", "-", "-", "-", "-"])
            continue
        table.append([r["arch"], r["shape"], r["dominant"],
                      f"{r['compute_s']*1e3:.2f}", f"{r['memory_s']*1e3:.2f}",
                      f"{r['collective_s']*1e3:.2f}", f"{r['hlo_bytes_s']*1e3:.0f}",
                      f"{r['useful_ratio']:.2f}", f"{r['roofline_frac']:.3f}",
                      f"{r['mem_temp_gb']+r['mem_args_gb']:.1f}"])
    headers = ["arch", "shape", "dominant", "compute_ms", "memory_ms",
               "collective_ms", "hloB_ms", "useful", "roofline_frac", "mem_GB/dev"]
    print(f"== §Roofline ({mesh} pod, per device) ==")
    print(fmt_table(table, headers))
    write_report(f"roofline_{mesh}", {"rows": rows})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
