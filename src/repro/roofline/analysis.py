"""Three-term roofline analysis over the dry-run artifacts.

Per (arch × shape × mesh):
    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_wire_bytes / (chips × link_bw)

All HLO quantities come from the partitioned module via
``roofline.hlo_parse`` (per-device numbers × chips = the formulas' global
numerators — the division by chips cancels, so terms are computed from the
per-device values directly).  Wire-byte factors: ring all-reduce moves
≈2× the tensor per device; all-gather/reduce-scatter/all-to-all/permute ≈1×.

Hardware model: ``PEAKS``, keyed by the device kind a record names (as
JAX reports it, ``jax.devices()[0].device_kind``).  A kind missing from
the table is an error, never a default.

MODEL_FLOPS uses 6·N·D (train) or 2·N·D (forward-only), with N = active
params for MoE; the ratio MODEL_FLOPS/HLO_FLOPs exposes remat/redundancy
waste (remat recompute, causal-chunk waste, dispatch overhead).
"""

from __future__ import annotations

import glob
import json
import os
from typing import NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    link_bw: float           # ICI bytes/s per link
    hbm_bytes: float         # HBM capacity per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s ICI per chip over 4 links (50 GB/s each)
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None

WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def model_flops_global(rec: dict) -> float:
    """Analytic MODEL_FLOPS for the whole step (all chips)."""
    from repro.models.config import SHAPES
    cell = SHAPES[rec["shape"]]
    n_active = rec["info"]["active_params"]
    if cell.step == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.step == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * cell.global_batch


def analyze_record(rec: dict) -> Optional[dict]:
    if "skipped" in rec or "error" in rec:
        return None
    hs = rec["hlo_stats"]
    chips = rec["n_devices"]
    peak = peaks_for(rec["device_kind"])
    flops_dev = hs["flops"]
    # fused byte model (TPU-like) when available, else conservative
    hbm_dev = hs.get("hbm_bytes_fused", hs["hbm_bytes"])
    wire_dev = sum(WIRE_FACTOR.get(k, 1.0) * v
                   for k, v in hs["collectives"].items())

    t_compute = flops_dev / peak.flops
    t_memory = hbm_dev / peak.hbm_bw
    t_coll = wire_dev / peak.link_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_global(rec)
    hlo_global = flops_dev * chips
    useful = mf / hlo_global if hlo_global else 0.0
    # ideal step time: compute floor, and for serving steps also the
    # unavoidable HBM floor (params + cache must be read once per step)
    t_ideal = (mf / chips) / peak.flops
    from repro.models.config import SHAPES
    step_kind = SHAPES[rec["shape"]].step
    if step_kind == "decode":
        floor_bytes = (2.0 * rec["info"]["active_params"]
                       + rec["info"].get("cache_bytes", 0)) / chips
        t_ideal = max(t_ideal, floor_bytes / peak.hbm_bw)
    # roofline fraction: ideal over the dominant term's cost
    t_dom = terms[dominant]
    frac = t_ideal / t_dom if t_dom > 0 else 0.0
    mem = rec["memory_analysis"]
    hbm_per_dev = (mem["argument_bytes"] + mem["output_bytes"]
                   + mem["temp_bytes"] - mem["alias_bytes"])
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "terms_s": {k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": round(useful, 4),
        "roofline_fraction": round(frac, 4),
        "device_bytes": hbm_per_dev,
        "fits_16gb": hbm_per_dev < peak.hbm_bytes,
        "collectives_dev": hs["collectives"],
        "unknown_trip_loops": hs.get("unknown_trip_loops", 0),
    }


def suggestion(row: dict) -> str:
    d = row["dominant"]
    if d == "compute":
        if row["useful_ratio"] < 0.35:
            return ("compute-bound with low useful ratio — cut remat "
                    "recompute / causal-chunk waste")
        return "compute-bound near peak — only algorithmic changes help"
    if d == "memory":
        return ("memory-bound — fuse/cast (bf16 cache, wider blocks), "
                "raise arithmetic intensity per HBM byte")
    return ("collective-bound — reshard to cut all-reduce volume, overlap "
            "collectives with compute, or compress cross-pod traffic")


def load_all(art_dir: str = "artifacts/dryrun") -> list[dict]:
    recs = []
    for p in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(p) as f:
            recs.append(json.load(f))
    return recs


def table(art_dir: str = "artifacts/dryrun", mesh: str = "16x16") -> str:
    """Markdown roofline table (single-pod by default, per the brief)."""
    rows, skipped = [], []
    for rec in load_all(art_dir):
        if rec.get("mesh") != mesh:
            continue
        if "skipped" in rec:
            skipped.append(rec)
            continue
        r = analyze_record(rec)
        if r:
            rows.append(r)
    lines = [
        f"| arch | shape | compute (s) | memory (s) | collective (s) | "
        f"dominant | MODEL/HLO | roofline frac | bytes/dev | fits 16GB |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        t = r["terms_s"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute']:.4f} | "
            f"{t['memory']:.4f} | {t['collective']:.4f} | {r['dominant']} | "
            f"{r['useful_ratio']:.3f} | {r['roofline_fraction']:.3f} | "
            f"{r['device_bytes'] / 1e9:.2f} GB | "
            f"{'yes' if r['fits_16gb'] else 'NO'} |")
    for s in sorted(skipped, key=lambda x: (x["arch"], x["shape"])):
        lines.append(f"| {s['arch']} | {s['shape']} | — | — | — | skipped | "
                     f"— | — | — | — |")
    return "\n".join(lines)


def pick_hillclimb_cells(art_dir: str = "artifacts/dryrun") -> dict:
    """worst roofline fraction / most collective-bound / most representative."""
    rows = [analyze_record(r) for r in load_all(art_dir)
            if r.get("mesh") == "16x16"]
    rows = [r for r in rows if r]
    worst = min(rows, key=lambda r: r["roofline_fraction"])
    coll = max(rows, key=lambda r: r["terms_s"]["collective"]
               / max(sum(r["terms_s"].values()), 1e-12))
    return {"worst": worst, "collective": coll}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--art", default="artifacts/dryrun")
    ap.add_argument("--out", default="artifacts/roofline.md")
    args = ap.parse_args()
    md = ["# Roofline table — single-pod (16×16 = 256 chips)", "",
          table(args.art, "16x16"), "",
          "# Multi-pod check (2×16×16 = 512 chips)", "",
          table(args.art, "2x16x16"), ""]
    rows = [analyze_record(r) for r in load_all(args.art)
            if r.get("mesh") == "16x16"]
    md.append("## Per-cell bottleneck notes (single-pod)")
    for r in sorted([x for x in rows if x],
                    key=lambda x: (x["arch"], x["shape"])):
        md.append(f"- **{r['arch']} × {r['shape']}** — dominant: "
                  f"{r['dominant']}; {suggestion(r)}")
    with open(args.out, "w") as f:
        f.write("\n".join(md))
    picks = pick_hillclimb_cells(args.art)
    print("worst roofline fraction:", picks["worst"]["arch"],
          picks["worst"]["shape"], picks["worst"]["roofline_fraction"])
    print("most collective-bound:", picks["collective"]["arch"],
          picks["collective"]["shape"], picks["collective"]["terms_s"])
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
