#!/usr/bin/env python3
"""Time the knob spaces of the ``deepseek-v2-lite`` configuration's install
on the chip, once, and train its decision models.

    python3 bench/install_deepseek.py --out <dir>      # on a TPU
    python3 bench/install_deepseek.py --train          # on any host

The configuration's install has a directory of its own
(``bench/data/install/deepseek-v2-lite/``: datasets, ``manifest.json``,
``models/``), so the other configurations' frozen files are never touched.
Each dataset is gathered by ``bench/gather_install.py``'s ``gather_one``
over the box its entry gives (``domain``, one bound per dim), with the
``pallas`` backend's calibration timer; the grouped gemm's operands carry
uneven group sizes from the seed, some groups empty.  As there, a dataset
that sampled any dims a cell asks for is refused.  ``--train`` trains the
models from the frozen datasets with the program's ``install_subroutine``
(``bench/common.py:train_install``) into the configuration's ``models``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common, gather_install  # noqa: E402

NAME = "deepseek-v2-lite"
CONFIG = common.BENCH / "configs" / f"{NAME}.json"


def sample_box(n: int, lo, hi, footprint, cap: int, seed: int) -> np.ndarray:
    """``gather_install.sample_box`` for any number of dims: the first
    ``n`` scrambled-Halton points, log-scaled per dim into [lo_i, hi_i],
    with ``footprint(dims) <= cap``."""
    from repro.core.halton import (BASES_2D, BASES_3D, BASES_4D,
                                   scrambled_halton)
    bases = {2: BASES_2D, 3: BASES_3D, 4: BASES_4D}[len(lo)]
    lo, hi = np.log(np.asarray(lo, float)), np.log(np.asarray(hi, float))
    out, start = [], 1
    while len(out) < n and start < 64 * n:
        u = scrambled_halton(2 * n, bases, seed=seed, start=start)
        start += 2 * n
        for row in np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64):
            if footprint(tuple(int(v) for v in row)) <= cap:
                out.append(row)
    return np.asarray(out[:n], dtype=np.int64)


def gather(out: Path, log) -> int:
    import jax
    config = common.load_json(CONFIG)
    inst = config["install"]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"JAX's backend is {devices[0].platform!r}, not a TPU: "
              f"install timings must come from the chip", file=sys.stderr)
        return 1
    out.mkdir(parents=True, exist_ok=True)
    # the grouped gemm's dims are four: the harness's sampler knows two or
    # three, so its gather draws from this one
    gather_install.sample_box = sample_box
    used = gather_install.cell_keys(NAME)
    manifest = {"device_kind": devices[0].device_kind,
                "jax": jax.__version__, "datasets": [],
                "commands": ["python3 bench/install_deepseek.py --out <dir>",
                             "python3 bench/install_deepseek.py --train"]}
    for entry in inst["datasets"]:
        tag = gather_install.tag_of(entry)
        n = entry.get("samples", inst["samples"])
        log(f"[gather] {NAME}: {tag}, {n} samples over {entry['domain']}")
        skipped: list = []
        ds = gather_install.gather_one(entry, dict(inst,
                                                   domain=entry["domain"]),
                                       n, log, skipped)
        sampled = {tuple(int(v) for v in d) for d in ds["dims"]}
        hit = sampled & used.get((entry["op"], ds["dtype_bytes"]), set())
        if hit:
            print(f"{tag}: sampled dims that a cell uses: {sorted(hit)}",
                  file=sys.stderr)
            return 1
        np.savez(out / Path(entry["file"]).name, **ds)
        row = {"config": NAME, "file": entry["file"],
               "samples": int(len(ds["dims"])),
               "knobs": len(json.loads(ds["knobs"])),
               "gather_seconds": round(ds["gather_seconds"], 1),
               "cell_dims_checked": sum(len(v) for v in used.values()),
               "dims_min": ds["dims"].min(axis=0).tolist(),
               "dims_max": ds["dims"].max(axis=0).tolist(),
               "skipped": skipped}
        manifest["datasets"].append(row)
        log(f"[gather] {json.dumps(row)}")
        (out / "manifest.json").write_text(json.dumps(manifest, indent=1)
                                           + "\n")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=str(common.BENCH / "data" / "install"
                                        / NAME))
    p.add_argument("--train", action="store_true",
                   help="gather nothing: train the decision models from the "
                        "frozen datasets (on any host)")
    args = p.parse_args(argv)
    if args.train:
        config = common.load_json(CONFIG)
        store = common.ROOT / config["install"]["models"]
        shutil.rmtree(store, ignore_errors=True)
        common.train_install(config, store)
        return 0
    common.enable_compile_cache()
    return gather(Path(args.out), lambda m: print(m, flush=True))


if __name__ == "__main__":
    sys.exit(main())
