"""Open-loop Level-3 traffic through ``BlasService``: a generator submits
one request per arrival of a Poisson process at the traffic file's rate,
each a resident shape drawn from a Zipf law over the pool, and never waits
for a result; the service (``backend="pallas"``, its defaults) buckets
requests by shape, pads each stack to a power of two and runs it as one
``run_op`` call on the installed runtime.  The seed sets the operand values,
the arrival times, the shapes drawn and which of a shape's ``variants``
operand sets each request carries, so the slots of one stack hold different
inputs; the pool and the rate are fixed by the traffic file.

The check holds single requests to the float64 reference: a seeded sample
of the requests completed inside the window (each result kept under its
request), and, after the window, a burst of ``check_stack`` requests of
distinct variants per sampled shape, submitted together so that they run as
one padded stack.  A result handed to the wrong slot, a filler row's result,
or one slot's result for all, comes out not correct.

``blas_tflops`` counts the algorithmic operations of the requests completed
inside the window (padding not counted) over the window."""

from __future__ import annotations

import threading
import time

import numpy as np

from bench import common, yardstick
from bench.drivers.blas_stream import operand_shapes
from bench.reference import l3 as ref

#: the stack widths the service can run (powers of two up to max_batch)
WIDTHS = (1, 2, 4, 8, 16, 32)
#: requests of a window whose results are kept for the check
KEEP = 64


def _pool(cell) -> list:
    return [(op, tuple(dims)) for op, dims in cell["traffic_data"]["pool"]]


def decision_keys(cell) -> list:
    import jax.numpy as jnp
    nbytes = jnp.dtype(cell["config_data"]["dtype"]).itemsize
    return [(op, nbytes, dims) for op, dims in _pool(cell)]


def arrivals(seed: int, t: dict, seconds: float, n_shapes: int):
    """(times from the window's start, pool index) of every arrival."""
    rng = np.random.default_rng([seed, 4])
    n = int(t["rate_per_s"] * seconds * 1.2) + 64
    times = np.cumsum(rng.exponential(1.0 / t["rate_per_s"], n))
    times = times[times < seconds]
    p = 1.0 / np.arange(1, n_shapes + 1, dtype=np.float64) ** t["zipf_a"]
    return times, rng.choice(n_shapes, size=times.size, p=p / p.sum())


def variants(seed: int, t: dict, n: int) -> np.ndarray:
    """The operand set each of ``n`` arrivals carries."""
    return np.random.default_rng([seed, 6]).integers(t["variants"], size=n)


def setup(run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import default_knob, run_op
    from repro.serving.service import BlasService
    cell, st = run.cell, run.state
    st["pool"] = pool = _pool(cell)
    dtype = np.dtype(jnp.dtype(cell["config_data"]["dtype"]))
    st["rt"] = rt = common.install_runtime(cell["config_data"], log=run.log)
    rng = np.random.default_rng([run.seed, 1])
    st["operands"] = [[tuple(rng.standard_normal(s).astype(dtype)
                             for s in operand_shapes(op, dims))
                       for _ in range(cell["traffic_data"]["variants"])]
                      for op, dims in pool]
    evals0 = rt.stats.model_evals
    # every (shape, width) program the service can run, compiled now with
    # the knob it will pick
    for (op, dims), (xs, *_) in zip(pool, st["operands"]):
        knob = rt.select_or_default(op, dims, dtype.itemsize,
                                    default_knob(op), backend="pallas")
        for w in WIDTHS:
            stack = tuple(np.broadcast_to(x, (w,) + x.shape) for x in xs)
            jax.block_until_ready(run_op(op, stack, backend="pallas",
                                         knob=knob, runtime=rt,
                                         stacked=True))
    run.counters["setup_model_evals"] = rt.stats.model_evals - evals0
    st["svc"] = BlasService(runtime=rt)
    for (op, _), (xs, *_) in zip(pool, st["operands"]):  # one served round
        st["svc"].submit(op, xs).result()


def _stats(svc) -> dict:
    s = svc.stats
    return {"completed": s.completed, "failed": s.failed,
            "padded": s.padded_items, "queue_s": s.queue_sum,
            "batches": s.batches}


def window(run, seconds: float, log_pending: list | None = None) -> None:
    """One window at the traffic's rate.  ``log_pending``, if given, gets
    (seconds from the start, requests in flight) about every 0.5 s."""
    st, t = run.state, run.cell["traffic_data"]
    svc, pool = st["svc"], st["pool"]
    times, picks = arrivals(run.seed, t, seconds, len(pool))
    carry = variants(run.seed, t, times.size)
    keep = set(np.random.default_rng([run.seed, 7]).choice(
        times.size, size=min(KEEP, times.size), replace=False).tolist())
    work = [yardstick.l3_flops(op, dims) for op, dims in pool]
    evals0 = st["rt"].stats.model_evals
    done: list = []                       # (t_done, pool index, ok)
    # request -> (t_done, pool index, variant, result)
    st["kept"] = kept = {}
    lock = threading.Lock()

    def finished(j, i):
        def cb(fut):
            ok = fut.exception() is None
            td = time.perf_counter()
            with lock:
                done.append((td, i, ok))
                if ok and j in keep:
                    kept[j] = (td, i, int(carry[j]), fut.result())
        return cb

    late = []
    before = _stats(svc)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    next_log = 0.0
    with run.annotate("bench.window"):
        for j, (at, i) in enumerate(zip(times, picks)):
            now = time.perf_counter() - t0
            if now >= seconds:          # a throttled generator stops here
                break
            if at > now:
                time.sleep(at - now)
            late.append(time.perf_counter() - t0 - at)
            op = pool[i][0]
            try:
                svc.submit(op, st["operands"][i][carry[j]]
                           ).add_done_callback(finished(j, int(i)))
            except Exception as e:          # counted, never hidden
                with lock:
                    done.append((time.perf_counter(), int(i), False))
                run.log(f"[window] submit {op} failed: {e!r}")
            if log_pending is not None and at >= next_log:
                log_pending.append((at, svc._pending))
                next_log += 0.5
        rest = t_end - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    after = _stats(svc)
    with lock:
        inside = [(i, ok) for td, i, ok in done if td <= t_end]
        st["kept"] = {j: v for j, v in kept.items() if v[0] <= t_end}
    svc.drain()
    with lock:
        failed = sum(1 for _, _, ok in done if not ok)
    run.counters["window_model_evals"] = st["rt"].stats.model_evals - evals0
    submitted = len(late)
    run.attempted, run.failed, run.window_s = submitted, failed, seconds
    flops = sum(work[i] for i, ok in inside if ok)
    run.metrics["blas_tflops"] = flops / seconds / 1e12
    late = np.asarray(late) if late else np.zeros(1)
    run.raw.update(
        flops=flops, submitted=submitted,
        completed_in_window=sum(1 for _, ok in inside if ok),
        late_p99_ms=1e3 * float(np.quantile(late, 0.99)),
        **{k: after[k] - before[k] for k in after})
    run.log(f"[window] {submitted} of {len(times)} arrivals submitted, "
            f"{run.raw['completed_in_window']} completed inside, "
            f"{after['batches'] - before['batches']} batches, generator "
            f"late p99 {run.raw['late_p99_ms']:.2f} ms")


def _compare(run, op, host, got) -> float:
    got = ref.control(op, host) if run.control else run.fault("answer", got)
    return ref.rel_err(got, ref.oracle(op, host))


def check(run) -> list:
    """Single requests against the float64 reference: ``check_per_op``
    requests per op drawn from those kept that completed inside the
    window, then, for each of their shapes, a burst of ``check_stack``
    requests of distinct variants served as one stack."""
    st, pool = run.state, run.state["pool"]
    t = run.cell["traffic_data"]
    svc, kept = st["svc"], st.get("kept", {})
    rng = np.random.default_rng([run.seed, 5])
    picks = []
    for op in dict.fromkeys(op for op, _ in pool):
        idx = sorted(j for j, v in kept.items() if pool[v[1]][0] == op)
        picks += sorted(rng.choice(idx, size=min(t["check_per_op"],
                                                 len(idx)),
                                   replace=False).tolist())
    errs = []
    for j in picks:
        _, i, v, got = kept[j]
        op, dims = pool[i]
        errs.append(_compare(run, op, st["operands"][i][v], got))
        run.log(f"[check] request {j}: {op} {dims} variant {v}: "
                f"max_rel_err={errs[-1]:.3e}")
    b0 = svc.stats.batches
    shapes = list(dict.fromkeys(kept[j][1] for j in picks))
    for i in shapes:
        op, dims = pool[i]
        hosts = st["operands"][i][:t["check_stack"]]
        futs = [svc.submit(op, xs) for xs in hosts]
        for v, (xs, fut) in enumerate(zip(hosts, futs)):
            errs.append(_compare(run, op, xs, fut.result()))
            run.log(f"[check] burst {op} {dims} variant {v}: "
                    f"max_rel_err={errs[-1]:.3e}")
    run.log(f"[check] {len(picks)} window requests, {len(shapes)} bursts "
            f"of {t['check_stack']} in {svc.stats.batches - b0} batches")
    worst = max(errs) if errs else float("inf")
    if any(e != e for e in errs):
        worst = float("nan")
    svc.close()
    st.clear()
    return [("max_rel_err", worst)]
