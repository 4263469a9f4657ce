#!/usr/bin/env python3
"""Sweep the open-loop rate of the ``l3-f32.served`` cell, once, to find
the knee its traffic file's rate is set from.

    python3 bench/sweep_served.py --seed <n> --rates 500,1000,2000 --seconds 30

One process sets the cell up as ``bench/run.py`` does (the frozen ``l3-f32``
install, the resident operands, every (shape, width) program compiled, the
service started), then runs one window per rate with the cell's driver and
prints a JSON line for each: requests submitted and completed inside the
window, the generator's lateness, and the requests in flight (sampled about
every 0.5 s).  A rate is sustained when the requests in flight at the
window's end and over its second half stay within two full stacks
(``2 · max_batch``) and the generator keeps to its schedule (99th
percentile lateness under 50 ms): the queue does not grow.  The knee is the
highest sustained rate; the last line gives it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402

CELL = "l3-f32.served"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second, ascending")
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    cell = common.cell(CELL)
    common.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("the sweep runs on a TPU", file=sys.stderr)
        return 1
    from bench import yardstick
    from bench.run import Run
    drv = common.driver(cell["traffic_data"]["driver"])
    run = Run(cell, args.seed,
              peak=yardstick.peaks(jax.devices()[0].device_kind))
    drv.setup(run)
    cap = 2 * run.state["svc"].config.max_batch
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell["traffic_data"]["rate_per_s"] = rate
        pending: list = []
        drv.window(run, args.seconds, log_pending=pending)
        half = [n for t, n in pending if t >= args.seconds / 2]
        row = {"rate_per_s": rate, "submitted": run.raw["submitted"],
               "completed_in_window": run.raw["completed_in_window"],
               "blas_tflops": run.metrics["blas_tflops"],
               "late_p99_ms": run.raw["late_p99_ms"],
               "in_flight_end": pending[-1][1] if pending else None,
               "in_flight_max_second_half": max(half, default=None),
               "failed": run.failed}
        row["sustained"] = bool(
            run.failed == 0 and half and max(half) <= cap
            and run.raw["submitted"] - run.raw["completed_in_window"] <= cap
            and run.raw["late_p99_ms"] < 50.0)
        if row["sustained"]:
            knee = rate
        print(json.dumps(row), flush=True)
    run.state["svc"].close()
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 1.25 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
