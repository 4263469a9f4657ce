#!/usr/bin/env python3
"""Time the tuner's knob space on the chip, once, for the frozen install.

    python3 bench/gather_install.py --out bench/data/install

For every configuration in ``BENCHMARK.json`` that has an ``install``
section, and every (op, dtype) dataset it lists, this samples the op's free
dims by scrambled Halton over the configuration's domain and times every
knob of the program's knob space with the ``pallas`` backend's own
calibration timer (``Backend.timer_fn``: one warm-up call, then the median
of two, each blocked on its result).  A domain with one ``lo``/``hi`` is
sampled by the program's ``gather`` (the path ``install_subroutine`` takes);
one with a bound per dim is mapped per dim.  The datasets are written as
``pallas__<op>_<dtype>.npz`` beside a ``manifest.json``.

It refuses to write a dataset that sampled any dims a cell uses, since the
benchmark would then measure a decision on a training point.  A TPU is
required: timings from any other device would be meaningless.

    python3 bench/gather_install.py --train

then trains each configuration's decision models from its datasets with the
program's ``install_subroutine`` (``repro.launch.calibrate``'s defaults and
a fixed seed) into the ``models`` directory the configuration names, which
the benchmark loads.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402

DTYPE_TAG = {"float32": "f32", "bfloat16": "bf16"}
#: programs compiled at once before a dims is timed
COMPILE_JOBS = 8


def tag_of(entry: dict) -> str:
    return f"{entry['op']}_{DTYPE_TAG[entry['dtype']]}"


def sample_box(n: int, lo, hi, footprint, cap: int, seed: int) -> np.ndarray:
    """The first ``n`` scrambled-Halton dims, log-scaled per dim into [lo_i,
    hi_i], with ``footprint(dims) <= cap``."""
    from repro.core.halton import BASES_2D, BASES_3D, scrambled_halton
    nd = len(lo)
    bases = BASES_3D if nd == 3 else BASES_2D
    lo, hi = np.log(np.asarray(lo, float)), np.log(np.asarray(hi, float))
    out, start = [], 1
    while len(out) < n and start < 64 * n:
        u = scrambled_halton(2 * n, bases[:nd], seed=seed, start=start)
        start += 2 * n
        for row in np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64):
            if footprint(tuple(int(v) for v in row)) <= cap:
                out.append(row)
    return np.asarray(out[:n], dtype=np.int64)


def cell_keys(name: str) -> dict:
    """{(op, dtype_bytes): {dims}} that the benchmark's cells of config
    ``name`` ask the decision runtime for."""
    spec = common.benchmark()
    keys: dict = {}
    for w in spec["workloads"]:
        if w["config"] != name:
            continue
        c = common.cell(w["name"])
        drv = common.driver(c["traffic_data"]["driver"])
        for op, nbytes, dims in drv.decision_keys(c):
            keys.setdefault((op, nbytes), set()).add(tuple(dims))
    return keys


def compiled_first(timer, be, op: str, dtype, space, jobs: int):
    """``timer`` with every knob's program compiled, ``jobs`` at a time,
    when it first meets a dims: the gather is bound by one compile per
    (dims, knob), and compiles run in parallel where timings may not."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    seen: set = set()

    def timed(dims, knob):
        if jobs > 1 and dims not in seen:
            seen.add(dims)
            xs = be.prepare(be.make_operands(op, dims, dtype, seed=0))
            with ThreadPoolExecutor(jobs) as pool:
                list(pool.map(lambda k: jax.block_until_ready(
                    be.execute(op, xs, k)), space.candidates))
            del xs
        return timer(dims, knob)

    return timed


def gather_one(entry: dict, inst: dict, samples: int, log,
               skipped: list) -> dict:
    import jax.numpy as jnp
    from repro.backends import get_backend
    from repro.core.dataset import gather
    from repro.core.features import footprint_words
    op, dtype = entry["op"], jnp.dtype(entry["dtype"])
    be = get_backend("pallas")
    space = be.knob_space(op, sizes=tuple(inst["sizes"]))
    timer = compiled_first(be.timer_fn(op, dtype), be, op, dtype, space,
                           COMPILE_JOBS)
    dom = inst["domain"]
    nbytes = dtype.itemsize
    progress = lambda i, n: log(f"  {op}/{dtype.name}: {i}/{n} samples")
    t0 = time.perf_counter()
    if len(dom["lo"]) == 1:
        ds = gather(op, space, timer, n_samples=samples, dim_lo=dom["lo"][0],
                    dim_hi=dom["hi"][0],
                    max_footprint_bytes=dom["max_footprint_bytes"],
                    dtype_bytes=nbytes, seed=inst["halton_seed"],
                    progress=progress)
        dims, times = ds.dims, ds.times
    else:
        # a knob the program cannot compile at some dims makes that sample
        # unusable (the tuner takes no missing times): it is skipped, and
        # recorded, and the next Halton point is taken
        cands = sample_box(4 * samples, dom["lo"], dom["hi"],
                           lambda d: footprint_words(op, d) * nbytes,
                           dom["max_footprint_bytes"], inst["halton_seed"])
        dims, rows = [], []
        for d in cands:
            d = tuple(int(v) for v in d)
            try:
                rows.append([timer(d, knob) for knob in space])
            except Exception as e:      # a compile refusal (Mosaic) or a
                # runtime error: recorded in the manifest, never hidden
                skipped.append({"dims": d, "error": str(e).splitlines()[0]})
                log(f"  {op}/{dtype.name}: {d} skipped: {skipped[-1]['error']}")
                continue
            dims.append(d)
            progress(len(dims), samples)
            if len(dims) == samples:
                break
        dims, times = np.asarray(dims, np.int64), np.asarray(rows)
    return {"dims": dims, "times": times,
            "knobs": json.dumps([k.dict for k in space.candidates]),
            "dtype_bytes": nbytes,
            "gather_seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=str(common.BENCH / "data" / "install"))
    p.add_argument("--samples", type=int, default=0,
                   help="override every dataset's sample count")
    p.add_argument("--only", default="",
                   help="comma-separated op_dtype tags, e.g. gemm_bf16")
    p.add_argument("--train", action="store_true",
                   help="gather nothing: train every configuration's "
                        "decision models from its datasets (on any host); "
                        "with --only, just those, keeping the others")
    args = p.parse_args(argv)
    only = {t for t in args.only.split(",") if t}
    if args.train:
        for c in common.benchmark()["configs"]:
            config = common.load_json(common.ROOT / c["file"])
            store = common.ROOT / config["install"]["models"]
            if only:
                config["install"]["datasets"] = [
                    e for e in config["install"]["datasets"] if tag_of(e) in only]
            else:
                shutil.rmtree(store, ignore_errors=True)
            common.train_install(config, store)
        return 0

    common.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"JAX's backend is {devices[0].platform!r}, not a TPU: "
              f"install timings must come from the chip", file=sys.stderr)
        return 1
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log = lambda m: print(m, flush=True)
    manifest = {"device_kind": devices[0].device_kind,
                "jax": jax.__version__, "datasets": []}
    if (out / "manifest.json").exists():     # keep what is not gathered anew
        manifest = common.load_json(out / "manifest.json")
        if manifest["device_kind"] != devices[0].device_kind:
            print(f"{out} holds timings of a {manifest['device_kind']}",
                  file=sys.stderr)
            return 1
    for c in common.benchmark()["configs"]:
        config = common.load_json(common.ROOT / c["file"])
        inst = config.get("install")
        if not inst:
            continue
        used = cell_keys(config["name"])
        for entry in inst["datasets"]:
            tag = tag_of(entry)
            if only and tag not in only:
                continue
            n = args.samples or entry.get("samples", inst["samples"])
            log(f"[gather] {config['name']}: {tag}, {n} samples")
            skipped: list = []
            ds = gather_one(entry, inst, n, log, skipped)
            sampled = {tuple(int(v) for v in d) for d in ds["dims"]}
            hit = sampled & used.get((entry["op"], ds["dtype_bytes"]), set())
            if hit:
                print(f"{tag}: sampled dims that a cell uses: {sorted(hit)}",
                      file=sys.stderr)
                return 1
            path = out / Path(entry["file"]).name
            np.savez(path, **ds)
            row = {"config": config["name"], "file": entry["file"],
                   "samples": int(len(ds["dims"])),
                   "knobs": len(json.loads(ds["knobs"])),
                   "gather_seconds": round(ds["gather_seconds"], 1),
                   "cell_dims_checked": sum(len(v) for v in used.values()),
                   "dims_min": ds["dims"].min(axis=0).tolist(),
                   "dims_max": ds["dims"].max(axis=0).tolist(),
                   "skipped": skipped}
            manifest["datasets"] = [r for r in manifest["datasets"]
                                    if r["file"] != row["file"]] + [row]
            log(f"[gather] {json.dumps(row)}")
            (out / "manifest.json").write_text(
                json.dumps(manifest, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
