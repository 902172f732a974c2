"""§Roofline: three-term analysis from the dry-run artifacts.

Per (arch × shape) on the single-pod 16x16 mesh (256 chips):

  compute_s    = HLO_FLOPs_per_device / 197e12         (bf16 peak / chip)
  memory_s     = HLO_bytes_per_device / 819e9          (HBM bandwidth)
  collective_s = collective_bytes_per_device / 50e9    (~1 ICI link)

HLO terms come from the loop-accurate 1L/2L-unrolled extrapolation (see
``launch.dryrun.account_cell``); collective bytes are summed result-buffer
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops in post-SPMD HLO.  MODEL_FLOPS uses 6·N_active·D
(train) or 2·N_active·D (forward-only), giving the "useful fraction" that
catches remat/dispatch/replication waste.
"""

from __future__ import annotations

import glob
import json
import os

from repro.configs import ARCHS, SHAPES

PEAK_FLOPS = 197e12  # bf16 / chip (v5e)
HBM_BW = 819e9  # bytes/s
LINK_BW = 50e9  # bytes/s ICI per link
CHIPS = 256

__all__ = ["load_records", "analyze", "run_roofline"]


def load_records(root: str = "experiments/dryrun/pod16x16") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(root, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def model_flops_per_device(arch_name: str, shape_name: str) -> float:
    cfg = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    n_active = cfg.active_params_billions() * 1e9
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / CHIPS


def analyze(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    cost = rec.get("cost_accounted")
    if not isinstance(cost, dict) or "flops" not in cost:
        cost = rec.get("cost_analysis")
        if not isinstance(cost, dict):
            return None
    coll = rec.get("collectives", {})
    coll_bytes = coll.get("total_bytes", 0) if isinstance(coll, dict) else 0
    flops = cost.get("flops", 0.0)
    byts = cost.get("bytes accessed", 0.0)
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll_bytes / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec["arch"], rec["shape"])
    useful = mf / max(flops, 1.0)
    # roofline fraction: useful-math time over the binding term's time
    frac = (mf / PEAK_FLOPS) / max(terms[dominant], 1e-12)
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "kind": rec.get("kind", "?"),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_dev": mf,
        "hlo_flops_dev": flops,
        "useful_flop_ratio": useful,
        "roofline_fraction": frac,
        "state_gb_dev": rec.get("state_bytes_per_device", 0) / 1e9,
        "temp_gb_dev": (
            (rec.get("memory_analysis") or {}).get("temp_size_in_bytes", 0) / 1e9
            if isinstance(rec.get("memory_analysis"), dict)
            else None
        ),
    }


def run_roofline(root="experiments/dryrun/pod16x16", verbose=True,
                 out_md="experiments/roofline.md"):
    recs = load_records(root)
    if not recs:
        raise FileNotFoundError(
            f"no dry-run records under {root!r}; run repro.launch.dryrun first"
        )
    rows = [a for a in (analyze(r) for r in recs) if a]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    if verbose:
        hdr = (f"  {'arch':18s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s}"
               f" {'collect_s':>10s} {'bound':>10s} {'useful':>7s} {'roofl%':>7s}")
        print(hdr)
        for r in rows:
            print(
                f"  {r['arch']:18s} {r['shape']:12s} {r['compute_s']:10.4f}"
                f" {r['memory_s']:10.4f} {r['collective_s']:10.4f}"
                f" {r['dominant']:>10s} {r['useful_flop_ratio']:7.3f}"
                f" {100 * r['roofline_fraction']:7.2f}"
            )
    if out_md:
        os.makedirs(os.path.dirname(out_md), exist_ok=True)
        with open(out_md, "w") as f:
            f.write("| arch | shape | compute_s | memory_s | collective_s | "
                    "bound | useful flop ratio | roofline % | state GB/dev | temp GB/dev |\n")
            f.write("|---|---|---|---|---|---|---|---|---|---|\n")
            for r in rows:
                t = f"{r['temp_gb_dev']:.2f}" if r["temp_gb_dev"] is not None else "-"
                f.write(
                    f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
                    f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
                    f"{r['dominant']} | {r['useful_flop_ratio']:.3f} | "
                    f"{100 * r['roofline_fraction']:.2f} | "
                    f"{r['state_gb_dev']:.2f} | {t} |\n"
                )
    return rows
