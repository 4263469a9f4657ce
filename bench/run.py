#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration and a traffic mix, named in ``BENCHMARK.json``)
is set up (decision models, weights or operands made on the device from the
seed, every program it will run compiled or loaded from the compile cache in
``bench/.cache/jax``), then measured for ``--seconds``, then checked against
the plain reference once the program's state is freed.  With ``--trace 1``
the window is traced and the cell's per-layer metrics are printed instead of
its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared, with its limit.
The same numbers close standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.

``--control 1`` puts the reference, computed at the precision below the
configuration's, in the program's place for the check; it exists to show
that the check fails it, and is not part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402


class Run:
    """What a driver and the per-layer readers share about one run."""

    def __init__(self, cell: dict, seed: int, *, trace: bool = False,
                 control: bool = False, peak: dict | None = None,
                 faults: dict | None = None, log=None) -> None:
        self.cell, self.seed, self.trace, self.control = (cell, seed, trace,
                                                         control)
        self.peak = peak or {}
        self.faults = faults or {}
        self.log = log or (lambda m: print(m, file=sys.stderr, flush=True))
        self.state: dict = {}      # the driver's own (freed before the check)
        self.metrics: dict = {}    # end-to-end values
        self.raw: dict = {}        # counts for the per-layer readers
        self.counters: dict = {}   # program counters, printed
        self.attempted = self.failed = 0
        self.window_s = 0.0
        self.tr = None             # the reduced trace, with --trace 1

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def fault(self, kind: str, value, *context):
        """A fault planted where ``value`` is produced (tests only):
        ``faults[kind](value, *context)``."""
        f = self.faults.get(kind)
        return value if f is None else f(value, *context)


@contextlib.contextmanager
def gc_pauses():
    """The seconds of each garbage collection made inside the block."""
    pauses, start = [], []

    def note(phase, info):
        if phase == "start":
            start.append(time.perf_counter())
        elif start:
            pauses.append(time.perf_counter() - start.pop())

    gc.callbacks.append(note)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(note)


def execute(run: Run, seconds: float, devices, *, t_start: float) -> dict:
    """Set up, measure, read the device, check; returns the result."""
    from bench import compare, tracing
    drv = common.driver(run.cell["traffic_data"]["driver"])
    drv.setup(run)
    setup_s = time.perf_counter() - t_start
    run.log(f"[setup] {setup_s:.3f} s; model_evals in set-up "
            f"{run.counters.get('setup_model_evals')}")
    tdir = common.BENCH / ".cache" / "trace" / run.cell["name"]
    # what set-up left on the heap is never garbage: a full collection that
    # walks it inside the window stalls the caller for no work of the cell
    gc.collect()
    gc.freeze()
    with gc_pauses() as pauses:
        if run.trace:
            with tracing.capture(tdir):
                drv.window(run, seconds)
        else:
            drv.window(run, seconds)
    gc.unfreeze()
    run.log(f"[window] {run.window_s:.3f} s, {run.attempted} attempted, "
            f"{run.failed} failed; model_evals in the window "
            f"{run.counters.get('window_model_evals')}; garbage collections "
            f"{len(pauses)}, {sum(pauses):.4f} s, longest "
            f"{max(pauses, default=0.0):.4f} s")
    device = common.device_info(devices)
    numbers = drv.check(run)
    correct, rows = compare.judge(run.cell["name"], numbers)
    correct = correct and run.failed == 0
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed}
    if run.trace:
        run.tr = tracing.load(tdir)
        lo, hi = run.tr.segment("bench.window")
        device["busy_s"] = run.tr.busy_s(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        metrics = {}
        for m in run.cell["per_layer"]:
            value = common.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": run.tr.top_ops(lo, hi),
                               "idle_gaps": run.tr.idle_gaps(lo, hi)}
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        values = dict(run.metrics, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in run.cell["end_to_end"]}
        result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = common.cell(args.workload)
    common.seed_words(args.seed)

    common.enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell["chips"]:
        print(f"this cell runs on {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {platform!r} device(s)", file=sys.stderr)
        return 1
    from bench import yardstick
    run = Run(cell, args.seed, trace=bool(args.trace),
              control=bool(args.control),
              peak=yardstick.peaks(devices[0].device_kind))
    result = execute(run, args.seconds, devices, t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
