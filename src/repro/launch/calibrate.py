"""Install-time calibration driver (paper Fig. 1a) — the ADSALA "installation".

Runs the full pipeline per BLAS L3 subroutine × precision:

    Halton sampling → wall-clock timing sweep of the host's black-box BLAS
    (kernels.cpu_blocked on CPU hosts; kernels.ops on TPU) → features →
    LOF → Yeo-Johnson/standardize/corr-prune → per-model hyper-tuning →
    estimated-speedup model selection → persist artifacts + datasets.

Usage:
    PYTHONPATH=src python -m repro.launch.calibrate \
        --out runs/adsala --samples 100 --ops gemm,symm --precisions s,d \
        --backend cpu_blocked

``--backend`` selects the execution backend being calibrated (the paper's
MKL-vs-BLIS axis): each artifact is backend-tagged, so one store can hold
the model sets of several backends side by side.

Precisions: s = float32, d = float64 (paper's SGEMM/DGEMM pairing; on TPU
targets the pair maps to bf16/f32 — DESIGN.md §2).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.backends import get_backend
from repro.core import (ModelRegistry, install_subroutine)
from repro.launch.compile_cache import enable_compile_cache

PRECISIONS = {"s": np.float32, "d": np.float64}
DEFAULT_BACKEND = "cpu_blocked"


def calibrate_one(op: str, prec: str, out: Path, *, backend: str, samples: int,
                  dim_lo: int, dim_hi: int, footprint_mb: float,
                  sizes: tuple[int, ...], tune_trials: int, seed: int,
                  candidates=None, log=print) -> dict:
    dtype = PRECISIONS[prec]
    dtype_bytes = np.dtype(dtype).itemsize
    be = get_backend(backend)
    space = be.knob_space(op, sizes=sizes)
    timer = be.timer_fn(op, dtype)
    t0 = time.perf_counter()
    kw = {}
    if candidates:
        kw["candidates"] = candidates
    sub = install_subroutine(
        op, space, timer, n_samples=samples, dim_lo=dim_lo, dim_hi=dim_hi,
        max_footprint_bytes=int(footprint_mb * 1e6), dtype_bytes=dtype_bytes,
        tune_trials=tune_trials, seed=seed, backend=be.name,
        progress=lambda i, n: (log(f"  [{op}/{prec}] gathered {i}/{n}")
                               if i % 25 == 0 else None), **kw)
    wall = time.perf_counter() - t0
    reg = ModelRegistry(out / "models")
    path = reg.save(sub)

    # persist the training dataset for the heatmap figures (Fig. 4/5);
    # the default backend keeps the legacy untagged filename
    ds_dir = out / "datasets"
    ds_dir.mkdir(parents=True, exist_ok=True)
    ds_name = (f"{op}_{prec}.npz" if be.name == DEFAULT_BACKEND
               else f"{be.name}__{op}_{prec}.npz")
    np.savez(ds_dir / ds_name, dims=sub.dataset.dims,
             times=sub.dataset.times,
             knobs=json.dumps([k.dict for k in sub.dataset.knob_space]),
             default_idx=sub.dataset.default_knob_index())

    report = {
        "op": op, "prec": prec, "backend": be.name,
        "best_model": sub.model_name,
        "wall_seconds": round(wall, 1),
        "gather_seconds": round(sub.dataset.gather_seconds, 1),
        "n_samples": int(sub.dataset.n_samples),
        "n_knobs": len(space),
        "artifact": str(path),
        "models": [r.row() for r in sub.reports],
    }
    log(f"  [{be.name}:{op}/{prec}] done in {wall:.0f}s; "
        f"best={sub.model_name}")
    return report


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="runs/adsala")
    p.add_argument("--backend", default=DEFAULT_BACKEND)
    p.add_argument("--ops", default="gemm,symm,syrk,syr2k,trmm,trsm")
    p.add_argument("--precisions", default="s,d")
    p.add_argument("--samples", type=int, default=100)
    p.add_argument("--dim-lo", type=int, default=32)
    p.add_argument("--dim-hi", type=int, default=512)
    p.add_argument("--footprint-mb", type=float, default=6.0)
    p.add_argument("--sizes", default="64,128,256")
    p.add_argument("--tune-trials", type=int, default=3)
    p.add_argument("--candidates", default="")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    enable_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    sizes = tuple(int(s) for s in args.sizes.split(","))
    cands = tuple(c for c in args.candidates.split(",") if c) or None
    # merge with any prior report (partial recalibrations replace their rows)
    report_path = out / "calibration_report.json"
    reports = []
    if report_path.exists():
        reports = json.loads(report_path.read_text())
    for op in args.ops.split(","):
        for prec in args.precisions.split(","):
            print(f"[calibrate] {args.backend}:{op}/{prec} ...",
                  flush=True)
            entry = calibrate_one(
                op, prec, out, backend=args.backend,
                samples=args.samples, dim_lo=args.dim_lo,
                dim_hi=args.dim_hi, footprint_mb=args.footprint_mb,
                sizes=sizes, tune_trials=args.tune_trials, seed=args.seed,
                candidates=cands,
                log=lambda m: print(m, flush=True))
            reports = [r for r in reports
                       if not (r["op"] == op and r["prec"] == prec
                               and r.get("backend",
                                         DEFAULT_BACKEND) == args.backend)]
            reports.append(entry)
            (out / "calibration_report.json").write_text(
                json.dumps(reports, indent=2))
    print(f"[calibrate] all done → {out}/calibration_report.json", flush=True)


if __name__ == "__main__":
    sys.exit(main())
