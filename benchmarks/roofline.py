"""Roofline analysis (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh):
    compute term    = FLOPs             / (chips × peak MXU FLOP/s)
    memory term     = HBM bytes         / (chips × HBM bytes/s)
    collective term = collective bytes  / (chips × ICI bytes/s per link)

The machine constants are the published TPU v5e row of
:mod:`repro.kernels.hw_model` — the same ``HardwareModel`` the
kernel-variant cost model prices Pallas block configurations with, so a
kernel the selector calls compute-bound can never look memory-bound in
this table.  They are the chip's datasheet peaks, not measurements.

Two data sources, auto-selected:

* **dry-run artifacts** (``experiments/dryrun/*.json``): trip-count-scaled
  HLO analysis of the per-device partitioned module, when a prior
  dry-run produced them;
* **analytic fallback** (no artifacts): per-chip terms estimated straight
  from the architecture configs — weight/activation/KV-cache traffic and
  6ND (train) / 2ND (inference) FLOPs — so the benchmark always runs
  against the current package layout.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) global, /chips.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import ARCHS, SHAPES, get_config
from repro.kernels.hw_model import TPU_V5E

PEAK_FLOPS = TPU_V5E.peak_flops   # bf16 / chip
HBM_BW = TPU_V5E.hbm_bw           # bytes/s / chip
LINK_BW = TPU_V5E.link_bw         # bytes/s / link (ICI)

_BYTES_PER_PARAM = 2                 # bf16 weights
_ANALYTIC_CHIPS = 256
_ANALYTIC_ARCHS = ["llama2_1b", "gemma_2b", "granite_8b", "musicgen_medium"]


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    n = cfg.param_count(active_only=cfg.n_experts > 0)
    if spec["kind"] == "train":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 6.0 * n * tokens
    if spec["kind"] == "prefill":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * spec["global_batch"]


def _analytic_bytes(cfg, spec) -> Dict[str, float]:
    """Per-step global HBM + collective traffic estimated from the config.

    Deliberately coarse — the point is correct dominant-term
    classification (train compute-bound, decode memory-bound), not
    byte-exact accounting: weights stream once per step (three times
    under training: forward, backward, optimizer), activations pay a
    dozen round-trips per layer, decode re-reads the KV cache every
    token, and training all-reduces gradients (~2× payload on a ring).
    """
    n_params = cfg.param_count(active_only=cfg.n_experts > 0)
    param_b = n_params * _BYTES_PER_PARAM
    head_dim = cfg.head_dim or cfg.d_model // cfg.n_heads
    kind = spec["kind"]
    batch, seq = spec["global_batch"], spec["seq_len"]
    if kind == "train":
        tokens = batch * seq
        act_b = 12.0 * tokens * cfg.d_model * cfg.n_layers * _BYTES_PER_PARAM
        return dict(hbm=3.0 * param_b + act_b, coll=2.0 * param_b)
    if kind == "prefill":
        tokens = batch * seq
        act_b = 12.0 * tokens * cfg.d_model * cfg.n_layers * _BYTES_PER_PARAM
        return dict(hbm=param_b + act_b, coll=0.0)
    # decode: one token per sequence, full KV cache re-read per step
    kv_b = (2.0 * batch * seq * cfg.n_layers * cfg.n_kv_heads * head_dim
            * _BYTES_PER_PARAM)
    act_b = 12.0 * batch * cfg.d_model * cfg.n_layers * _BYTES_PER_PARAM
    return dict(hbm=param_b + kv_b + act_b, coll=0.0)


def _classify(flops: float, hbm: float, coll: float, chips: int,
              mf: float) -> Dict:
    t_c = flops / PEAK_FLOPS
    t_m = hbm / HBM_BW
    t_n = coll / LINK_BW
    dominant = max((("compute", t_c), ("memory", t_m), ("collective", t_n)),
                   key=lambda kv: kv[1])[0]
    return dict(
        compute_s=t_c, memory_s=t_m, collective_s=t_n, dominant=dominant,
        model_flops_per_chip=mf,
        useful_flop_ratio=(mf / flops) if flops else 0.0,
        roofline_fraction=(t_c / max(t_c, t_m, t_n))
        if (t_c or t_m or t_n) else 0.0,
    )


def analyze_record(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    chips = 512 if rec["mesh"] == "2x16x16" else 256
    sc = rec.get("scaled", {})
    mf = model_flops(rec["arch"], rec["shape"]) / chips
    row = _classify(sc.get("flops", 0.0), sc.get("hbm_bytes", 0.0),
                    sc.get("collective_bytes", 0.0), chips, mf)
    mem = rec.get("memory", {})
    row.update(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        mem_per_device_gib=mem.get("total_per_device_bytes", 0) / 2**30,
        fits_hbm=mem.get("total_per_device_bytes", 0) <= TPU_V5E.hbm_bytes)
    return row


def analytic_record(arch: str, shape_name: str,
                    chips: int = _ANALYTIC_CHIPS) -> Dict:
    """One roofline row estimated from the config registry alone."""
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    mf = model_flops(arch, shape_name) / chips
    traffic = _analytic_bytes(cfg, spec)
    row = _classify(mf, traffic["hbm"] / chips, traffic["coll"] / chips,
                    chips, mf)
    per_dev = (cfg.param_count(active_only=False) * _BYTES_PER_PARAM) / chips
    row.update(arch=arch, shape=shape_name, mesh=f"analytic/{chips}",
               mem_per_device_gib=per_dev / 2**30,
               fits_hbm=per_dev <= TPU_V5E.hbm_bytes)
    return row


def analytic_rows(archs: Optional[List[str]] = None,
                  shapes: Optional[List[str]] = None) -> List[Dict]:
    archs = archs if archs is not None else [
        a for a in _ANALYTIC_ARCHS if a in ARCHS]
    shapes = shapes if shapes is not None else list(SHAPES)
    return [analytic_record(a, s) for a in archs for s in shapes]


def load_all(dryrun_dir: str = "experiments/dryrun") -> List[Dict]:
    """Rows from dry-run artifacts; the analytic estimate when there are
    none (a fresh checkout runs the benchmark without any prior step)."""
    rows = []
    for f in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        rec = json.load(open(f))
        row = analyze_record(rec)
        if row is None:
            rows.append(dict(arch=rec.get("arch"), shape=rec.get("shape"),
                             mesh=rec.get("mesh"),
                             skipped=rec.get("skip_reason",
                                             rec.get("error", "?"))[:60]))
        else:
            rows.append(row)
    if not rows:
        rows = analytic_rows()
    return rows


def to_markdown(rows: List[Dict]) -> str:
    lines = [
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "dominant | useful-FLOP ratio | mem/dev GiB | fits 16G |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if "skipped" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"— | — | — | skipped: {r['skipped']} | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.4f} | {r['memory_s']:.4f} "
            f"| {r['collective_s']:.4f} | **{r['dominant']}** "
            f"| {r['useful_flop_ratio']:.2f} | {r['mem_per_device_gib']:.2f} "
            f"| {'yes' if r['fits_hbm'] else 'NO'} |")
    return "\n".join(lines)


def run(dryrun_dir: str = "experiments/dryrun"):
    return load_all(dryrun_dir)


if __name__ == "__main__":
    rows = load_all()
    print(to_markdown(rows))
    out = "experiments/roofline.md"
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(to_markdown(rows) + "\n")
    print(f"\nwritten {out}")
