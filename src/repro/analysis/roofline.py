"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Hardware model: the per-chip peaks of :data:`PEAKS`, keyed by jax's
``device_kind``; the dry-run's production meshes model TPU v5e pods
(:data:`DRYRUN_KIND`).

Per (arch x shape x mesh) cell, from the compiled (post-SPMD, per-device)
module:

  compute_s    = HLO_flops_per_device / peak_flops_per_chip
  memory_s     = HLO_bytes_per_device / hbm_bw
  collective_s = collective_operand_bytes_per_device / ici_bw

(cost_analysis() describes the per-partition program, so dividing by a
single chip's peaks is the "/ chips" normalization of the assignment's
formulas.)  MODEL_FLOPS uses 6*N*D (train) or 2*N*D (forward-only), with
N = active params for MoE; the ratio MODEL_FLOPS/HLO_flops exposes remat
recompute, padding and dispatch overheads.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path
from typing import Dict, Optional

import numpy as np

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip
# (4 links of 50 GB/s).  A kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: the chip the dry-run's production meshes (launch/mesh.py) model
DRYRUN_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak bf16 FLOP/s, HBM B/s and per-link ICI B/s of one chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} (add it to PEAKS with its source)"
        ) from None


RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def active_param_count(arch: str) -> int:
    """Total for dense; embed+attn+shared+topk/E of experts for MoE."""
    from repro.configs import get_arch
    from repro.models import build
    from repro.models.params import _iter_leaves

    cfg = get_arch(arch)
    model = build(cfg)
    total = 0
    for path, d in _iter_leaves(model.defs):
        n = int(np.prod(d.shape))
        if cfg.n_experts and "experts" in (d.axes or ()):
            n = int(n * cfg.topk / cfg.n_experts)
        total += n
    return total


def model_flops(rec: dict, n_active: int) -> float:
    tokens = rec["global_batch"] * (rec["seq_len"] if rec["kind"] in ("train", "prefill") else 1)
    mult = 6.0 if rec["kind"] == "train" else 2.0
    return mult * n_active * tokens


def analyze_cell(rec: dict, n_active: Optional[int] = None) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    cost = rec.get("cost_analysis") or {}
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = rec.get("collective_bytes") or {}
    coll_total = float(sum(coll.values()))
    peak = peaks(DRYRUN_KIND)
    compute_s = flops / peak["flops"]
    memory_s = bytes_acc / peak["hbm_bw"]
    collective_s = coll_total / peak["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    out = {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "kind": rec["kind"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "roofline_fraction": compute_s / bound if bound > 0 else 0.0,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "collective_bytes_per_dev": coll_total,
        "collective_breakdown": coll,
        "temp_bytes_per_dev": (rec.get("memory_analysis") or {}).get("temp_size_in_bytes"),
        "arg_bytes_per_dev": (rec.get("memory_analysis") or {}).get("argument_size_in_bytes"),
    }
    if n_active is not None:
        n_dev = rec.get("n_devices", 256)
        mf = model_flops(rec, n_active)
        out["model_flops_total"] = mf
        out["useful_flops_ratio"] = (mf / n_dev) / flops if flops else 0.0
    return out


def load_all(results_dir: Path = RESULTS_DIR):
    """Raw dry-run records, with scan-calibrated flops/bytes/collectives
    merged in when a calib__* file exists (memory_analysis always comes from
    the full-depth run — peak memory needs the real module)."""
    recs = []
    for p in sorted(results_dir.glob("*.json")):
        if p.name.startswith("calib__"):
            continue
        rec = json.loads(p.read_text())
        calib = results_dir / f"calib__{p.name}"
        if calib.exists():
            c = json.loads(calib.read_text())
            if c.get("status") == "ok" and rec.get("status") == "ok":
                rec["cost_analysis"] = {**(rec.get("cost_analysis") or {}), **c["cost_analysis"]}
                rec["collective_bytes"] = c["collective_bytes"]
                rec["calibrated"] = True
        recs.append(rec)
    return recs


def _fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def make_table(mesh: str = "pod", results_dir: Path = RESULTS_DIR, with_model_flops: bool = True) -> str:
    recs = [r for r in load_all(results_dir) if r.get("mesh") == mesh]
    n_active_cache: Dict[str, int] = {}
    rows = []
    for rec in recs:
        if rec.get("status") == "skipped":
            rows.append((rec["arch"], rec["shape"], "SKIP", rec.get("reason", "")[:60], "", "", "", ""))
            continue
        if rec.get("status") != "ok":
            rows.append((rec["arch"], rec["shape"], "ERR", rec.get("error", "")[:60], "", "", "", ""))
            continue
        na = None
        if with_model_flops:
            if rec["arch"] not in n_active_cache:
                n_active_cache[rec["arch"]] = active_param_count(rec["arch"])
            na = n_active_cache[rec["arch"]]
        a = analyze_cell(rec, na)
        rows.append(
            (
                a["arch"],
                a["shape"],
                _fmt_s(a["compute_s"]),
                _fmt_s(a["memory_s"]),
                _fmt_s(a["collective_s"]),
                a["dominant"],
                f"{a['roofline_fraction']:.2f}",
                f"{a.get('useful_flops_ratio', 0):.2f}" if na else "-",
            )
        )
    hdr = "| arch | shape | compute | memory | collective | dominant | roofline frac | useful/HLO |"
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        lines.append("| " + " | ".join(str(c) for c in r) + " |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    table = make_table(args.mesh)
    print(table)
    if args.out:
        Path(args.out).write_text(table + "\n")


if __name__ == "__main__":
    main()
