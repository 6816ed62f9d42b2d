import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct inputs on 512 host-platform placeholder
devices, and record memory_analysis / cost_analysis / per-device collective
bytes for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

The two lines above MUST stay the very first statements — jax locks the
device count at first backend initialization.

Usage:
  python -m repro.launch.dryrun --arch stablelm_3b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both --skip-done
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, cell_applicable, get_arch  # noqa: E402
from repro.dist import api as dist_api  # noqa: E402
from repro.dist.sharding import (  # noqa: E402
    batch_axes,
    cache_axes,
    make_rules,
    shardings_for_axes,
    train_state_axes,
)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import params as pp  # noqa: E402
from repro.train import make_train_step  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str):
    """Per-device bytes moved by each collective family, parsed from the
    post-SPMD HLO: for each collective op, sum its *operand* shapes (the
    text between the op's parentheses)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z\-]+)(?:-start|-done)?\(", ls)
        if not m:
            continue
        op = m.group(1)
        base = op.replace("-start", "").replace("-done", "")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        args = ls[ls.index(base) :]
        args = args[args.index("(") + 1 :]
        depth = 1
        body = []
        for ch in args:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            body.append(ch)
        body = "".join(body)
        total = sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(body))
        out[base] += total
        counts[base] += 1
    return out, counts


def _mem_analysis_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "host_argument_size_in_bytes",
        "host_output_size_in_bytes",
        "host_temp_size_in_bytes",
        "serialized_size_in_bytes",
    ]
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def _cost_analysis_dict(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {str(k): float(v) for k, v in dict(ca).items()}


def lower_and_analyze(cfg, cell, multi_pod: bool):
    """Lower + compile one (cfg, cell) on the production mesh; returns the
    cost/memory/collective analysis dict.  Shared by the main dry-run and the
    scan-calibration variants (analysis/calibrate)."""
    from repro.launch.specs import cell_input_specs

    t0 = time.time()
    spec = cell_input_specs(cfg, cell)
    model = spec["model"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, mesh, cell.global_batch)
    param_axes = pp.axes_tree(model.defs)
    params_sh = shardings_for_axes(param_axes, mesh, rules)

    with dist_api.activate(mesh, rules):
        if spec["kind"] == "train":
            step = make_train_step(cfg, model, mesh=mesh)
            state_sh = shardings_for_axes(train_state_axes(cfg, model), mesh, rules)
            batch_sh = shardings_for_axes(
                batch_axes(cfg, spec["fn_inputs"][1]), mesh, rules
            )
            jitted = jax.jit(
                step, in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
        elif spec["kind"] == "prefill":
            fn = model.prefill_fn
            batch_sh = shardings_for_axes(batch_axes(cfg, spec["fn_inputs"][1]), mesh, rules)
            cache_sds = jax.eval_shape(fn, *spec["fn_inputs"])[1]
            cache_sh = shardings_for_axes(
                cache_axes(cfg, cache_sds, mesh.shape["model"]), mesh, rules
            )
            logits_sh = shardings_for_axes(("batch", "vocab"), mesh, rules)
            jitted = jax.jit(
                fn, in_shardings=(params_sh, batch_sh), out_shardings=(logits_sh, cache_sh)
            )
        else:  # decode
            fn = model.decode_fn
            cache_sds = spec["fn_inputs"][1]
            cache_sh = shardings_for_axes(
                cache_axes(cfg, cache_sds, mesh.shape["model"]), mesh, rules
            )
            token_sh = shardings_for_axes(("batch",), mesh, rules)
            pos_sh = shardings_for_axes((), mesh, rules)
            logits_sh = shardings_for_axes(("batch", "vocab"), mesh, rules)
            jitted = jax.jit(
                fn,
                in_shardings=(params_sh, cache_sh, token_sh, pos_sh),
                out_shardings=(logits_sh, cache_sh),
                donate_argnums=(1,),
            )

        t_lower = time.time()
        lowered = jitted.lower(*spec["fn_inputs"])
        t_compile = time.time()
        compiled = lowered.compile()
        t_done = time.time()

        mem = _mem_analysis_dict(compiled)
        cost = _cost_analysis_dict(compiled)
        hlo = compiled.as_text()
        coll, coll_counts = collective_bytes(hlo)

    return {
        "status": "ok",
        "n_devices": int(mesh.devices.size),
        "param_count": pp.count_params(model.defs),
        "param_bytes_global": pp.bytes_params(
            model.defs, "bfloat16" if cfg.param_dtype == "bfloat16" else "float32"
        ),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "collective_bytes": coll,
        "collective_counts": coll_counts,
        "hlo_lines": len(hlo.splitlines()),
        "lower_s": round(t_compile - t_lower, 2),
        "compile_s": round(t_done - t_compile, 2),
        "total_s": round(t_done - t0, 2),
        "_hlo": hlo,
    }


def run_cell(arch: str, shape: str, multi_pod: bool, keep_hlo: bool = False):
    """Lower + compile one cell; returns a result dict."""
    cfg = get_arch(arch)
    cell = SHAPES[shape]
    ok, reason = cell_applicable(cfg, cell)
    mesh_name = "multipod" if multi_pod else "pod"
    base = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "kind": cell.kind,
        "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
    }
    if not ok:
        return {**base, "status": "skipped", "reason": reason}

    analysis = lower_and_analyze(cfg, cell, multi_pod)
    hlo = analysis.pop("_hlo")
    result = {**base, **analysis}
    if keep_hlo:
        hlo_path = RESULTS_DIR / f"{arch}__{shape}__{mesh_name}.hlo.txt"
        hlo_path.parent.mkdir(parents=True, exist_ok=True)
        hlo_path.write_text(hlo)
        result["hlo_path"] = str(hlo_path)
    # memory_analysis gives the fits-or-not answer; print per spec step 3
    print(f"[{arch} x {shape} x {mesh_name}] memory_analysis:", result["memory_analysis"])
    print(f"[{arch} x {shape} x {mesh_name}] cost_analysis:", result["cost_analysis"])
    return result


def cell_path(arch, shape, mesh_name) -> Path:
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_name}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument(
        "--calibrate", action="store_true",
        help="run the scan-calibration variants (analysis/calibrate) instead "
             "of the full-depth dry-run; writes calib__*.json",
    )
    args = ap.parse_args()
    compile_cache.enable()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if args.all else [args.arch]
    shapes = list(SHAPES) if args.all else ([args.shape] if args.shape else list(SHAPES))

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                if args.calibrate:
                    from repro.analysis import calibrate as cal

                    path = cal.cell_path(arch, shape, mesh_name)
                else:
                    path = cell_path(arch, shape, mesh_name)
                if args.skip_done and path.exists():
                    st = json.loads(path.read_text()).get("status")
                    if st in ("ok", "skipped"):
                        continue
                try:
                    if args.calibrate:
                        res = cal.calibrated_cell(arch, shape, mesh_name == "multipod")
                    else:
                        res = run_cell(arch, shape, mesh_name == "multipod", keep_hlo=args.keep_hlo)
                except Exception as e:  # record the failure — it's a bug to fix
                    res = {
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    failures.append((arch, shape, mesh_name, str(e)[:200]))
                path.write_text(json.dumps(res, indent=2))
                print(f"-> {path.name}: {res['status']} "
                      f"({res.get('total_s', '?')}s)", flush=True)
                jax.clear_caches()

    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells OK")


if __name__ == "__main__":
    main()
