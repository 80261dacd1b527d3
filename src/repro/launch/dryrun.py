import os
# 512 fake CPU devices stand in for the production meshes: pin the CPU
# platform (a TPU host would otherwise hand JAX its chips) and add the
# device count to any XLA_FLAGS the caller set
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512"]))

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and extract the roofline terms.

The lines above MUST run before any other import (jax locks the platform
and device count at first init); do not set them globally — smoke tests
and benches see 1 device.  Exits non-zero when any cell fails.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""

import argparse        # noqa: E402
import json            # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import numpy as np     # noqa: E402

from repro import configs                                  # noqa: E402
from repro.configs.shapes import SHAPES, SHAPE_ORDER, applicable  # noqa: E402
from repro.hw.tpu_spec import TPU_V5E                      # noqa: E402
from repro.launch import costing                           # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_axes  # noqa: E402
from repro.launch.specs import input_specs                 # noqa: E402
from repro.models import layers as L                       # noqa: E402

def run_cell(arch: str, shape: str, multi_pod: bool,
             save_hlo: str | None = None) -> dict:
    """Lower+compile one cell; returns the roofline record."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    data, model = mesh_axes(mesh)
    L.set_mesh_axes(data, model)
    cfg = configs.get(arch)
    t0 = time.time()
    fn, args, kind = input_specs(arch, shape, mesh)
    # buffer donation (perf iteration D2/T1): caches update in place for
    # serving; params/optimizer state update in place for training — without
    # donation XLA copies the full buffers every step.
    donate = {"train": (0, 1), "prefill": (1,), "decode": (1,)}[kind]
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    rec = {
        "arch": arch, "shape": shape, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(mesh.devices.size),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                rec[attr] = int(v)
        rec["per_device_bytes"] = (rec.get("argument_size_in_bytes", 0)
                                   + rec.get("temp_size_in_bytes", 0)
                                   + rec.get("output_size_in_bytes", 0))
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = str(e)

    agg = costing.costs_of(compiled)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
    spec = SHAPES[shape]
    t1 = time.time()
    # trip-count correction: scan bodies are counted once by cost_analysis
    try:
        corr = costing.corrected_costs(
            cfg, kind, spec.global_batch,
            spec.seq_len if kind != "decode" else spec.seq_len, mesh, agg)
        rec["probe_s"] = round(time.time() - t1, 1)
    except Exception as e:  # pragma: no cover
        rec["probe_error"] = str(e)
        corr = agg
    rec["flops"] = corr["flops"]                    # per device
    rec["hlo_bytes"] = corr["bytes"]                # per device
    rec["collectives"] = corr["collectives"]        # per device
    rec["raw_agg"] = {"flops": agg["flops"], "bytes": agg["bytes"],
                      "collective_bytes": agg["collectives"]["total"]}

    # three-term per-chip roofline (§Roofline): cost numbers are already
    # per-device, so chips=1 in the divisor.
    terms = TPU_V5E.roofline_terms(rec["flops"], rec["hlo_bytes"],
                                   rec["collectives"]["total"], 1)
    rec["roofline"] = terms
    # MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D=tokens=B
    if kind == "train":
        tokens = spec.global_batch * spec.seq_len
        model_flops = 6 * cfg.active_param_count() * tokens
    elif kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        model_flops = 2 * cfg.active_param_count() * tokens
    else:
        tokens = spec.global_batch
        model_flops = 2 * cfg.active_param_count() * tokens
    rec["model_flops"] = model_flops                # global
    total_hlo_flops = rec["flops"] * rec["chips"]
    rec["useful_flop_ratio"] = (model_flops / total_hlo_flops
                                if total_hlo_flops else None)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    archs = configs.ARCHS if args.arch == "all" or args.all else [args.arch]
    shapes = list(SHAPE_ORDER) if args.shape == "all" or args.all \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        cfg = configs.get(arch)
        for shape in shapes:
            ok, reason = applicable(cfg, shape)
            if not ok:
                records.append({"arch": arch, "shape": shape,
                                "skipped": reason})
                print(f"SKIP  {arch:22s} {shape:12s} {reason}")
                continue
            for multi in meshes:
                tag = "2x16x16" if multi else "16x16"
                try:
                    hlo = None
                    if args.hlo_dir:
                        os.makedirs(args.hlo_dir, exist_ok=True)
                        hlo = os.path.join(args.hlo_dir,
                                           f"{arch}_{shape}_{tag}.hlo")
                    rec = run_cell(arch, shape, multi_pod=multi,
                                   save_hlo=hlo)
                    records.append(rec)
                    r = rec["roofline"]
                    print(f"OK    {arch:22s} {shape:12s} {tag:8s} "
                          f"flops={rec['flops']:.3e} "
                          f"coll={rec['collectives']['total']:.3e}B "
                          f"bound={r['dominant']:10s} "
                          f"[lower {rec['lower_s']}s compile "
                          f"{rec['compile_s']}s]")
                except Exception as e:
                    records.append({"arch": arch, "shape": shape,
                                    "mesh": tag, "error": str(e)})
                    print(f"FAIL  {arch:22s} {shape:12s} {tag:8s} {e}")
                    traceback.print_exc()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out} ({len(records)} records)")
    failed = [r for r in records if "error" in r]
    if failed:
        sys.exit(f"dryrun: {len(failed)} of {len(records)} cells failed")


if __name__ == "__main__":
    main()
