import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: lower ONE cell with config patches, report the
scan-calibrated roofline terms and (optionally) the op-level HLO histogram,
so each hypothesis -> change -> re-lower -> re-analyse iteration is one
command.

  python -m repro.launch.hillclimb --arch qwen15_32b --shape train_4k \
      --patch remat=False --hlo
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
from pathlib import Path  # noqa: E402

from repro.analysis.roofline import DRYRUN_KIND, peaks  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "hillclimb"


def parse_patch(items):
    out = {}
    for it in items or []:
        k, v = it.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--patch", nargs="*", default=[])
    ap.add_argument("--tag", default="exp")
    ap.add_argument("--hlo", action="store_true", help="dump op-level histogram of the 1-layer unrolled module")
    ap.add_argument("--mem", action="store_true", help="also lower the FULL-depth module and print memory_analysis (peak temp)")
    args = ap.parse_args()
    compile_cache.enable()

    from repro.analysis import calibrate as cal
    from repro.analysis.hlo_ops import report
    from repro.configs import SHAPES, get_arch
    from repro.launch.dryrun import lower_and_analyze

    patch = parse_patch(args.patch)
    multi = args.mesh == "multipod"

    # monkey-patch get_arch inside calibrate so variants inherit the patch
    base_cfg = get_arch(args.arch)
    patched_cfg = dataclasses.replace(base_cfg, **patch)
    cal.get_arch = lambda name: patched_cfg  # type: ignore

    res = cal.calibrated_cell(args.arch, args.shape, multi)
    cost = res["cost_analysis"]
    coll = sum(res["collective_bytes"].values())
    peak = peaks(DRYRUN_KIND)
    compute_s = cost["flops"] / peak["flops"]
    memory_s = cost["bytes accessed"] / peak["hbm_bw"]
    collective_s = coll / peak["ici_bw"]
    print(f"== {args.arch} x {args.shape} x {args.mesh}  patch={patch}")
    print(f"   flops/dev {cost['flops']:.3e}  -> compute  {compute_s*1e3:10.1f} ms")
    print(f"   bytes/dev {cost['bytes accessed']:.3e}  -> memory   {memory_s*1e3:10.1f} ms")
    print(f"   coll /dev {coll:.3e}  -> collective {collective_s*1e3:8.1f} ms")
    print(f"   collective breakdown: { {k: round(v/1e9,2) for k,v in res['collective_bytes'].items()} } GB")

    if args.mem:
        cell = SHAPES[args.shape]
        full = lower_and_analyze(patched_cfg, cell, multi)
        ma = full["memory_analysis"] or {}
        print(f"   FULL-depth memory_analysis: temp {ma.get('temp_size_in_bytes',0)/1e9:.1f}GB  "
              f"args {ma.get('argument_size_in_bytes',0)/1e9:.1f}GB  "
              f"out {ma.get('output_size_in_bytes',0)/1e9:.1f}GB (per device)")
        res["memory_analysis"] = ma

    if args.hlo:
        cell = SHAPES[args.shape]
        one = dataclasses.replace(
            patched_cfg,
            n_layers=3 if patched_cfg.rglru else 1,
            n_enc_layers=1,
            unroll_layers=True,
        )
        out = lower_and_analyze(one, cell, multi)
        print(report(out["_hlo"]))

    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS / f"{args.tag}__{args.arch}__{args.shape}__{args.mesh}.json"
    out_path.write_text(json.dumps({**res, "patch": {k: str(v) for k, v in patch.items()}}, indent=2))
    print("->", out_path)


if __name__ == "__main__":
    main()
