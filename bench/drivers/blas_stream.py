"""Closed-loop Level-3 stream: one caller issues eager ``run_op`` calls with
the installed runtime over a pool of resident shapes, waiting on each result
before the next call.  The seed sets the operand values and the order of
each round over the pool; the pool is fixed by the traffic file."""

from __future__ import annotations

import time

import numpy as np

from bench import common, yardstick
from bench.reference import l3 as ref


def _pool(cell) -> list:
    return [(op, tuple(dims)) for op, dims in cell["traffic_data"]["pool"]]


def decision_keys(cell) -> list:
    import jax.numpy as jnp
    nbytes = jnp.dtype(cell["config_data"]["dtype"]).itemsize
    return [(op, nbytes, dims) for op, dims in _pool(cell)]


def operand_shapes(op: str, dims) -> list:
    if op == "gemm":
        m, k, n = dims
        return [(m, k), (k, n)]
    a, b = dims
    if op in ("syrk", "syr2k"):
        return [(a, b)] * (1 if op == "syrk" else 2)
    return [(a, a), (a, b)]           # symm, trmm, trsm


def make_operands(pool, dtype, key):
    """Every operand of the pool, made on the device in one jitted call.
    trsm's triangle gets a dominant diagonal so that the solve is well
    conditioned."""
    import jax
    import jax.numpy as jnp

    def build(key):
        out = []
        for i, (op, dims) in enumerate(pool):
            ks = jax.random.split(jax.random.fold_in(key, i), 2)
            xs = [jax.random.normal(k, s, jnp.float32)
                  for k, s in zip(ks, operand_shapes(op, dims))]
            if op == "trsm":
                xs[0] = xs[0] + dims[0] * jnp.eye(dims[0], dtype=jnp.float32)
            out.append(tuple(x.astype(dtype) for x in xs))
        return out

    return jax.jit(build)(key)


def setup(run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import default_knob, run_op
    cell, st = run.cell, run.state
    st["pool"] = pool = _pool(cell)
    st["dtype"] = dtype = jnp.dtype(cell["config_data"]["dtype"])
    st["rt"] = rt = common.install_runtime(cell["config_data"], log=run.log)
    st["operands"] = jax.block_until_ready(
        make_operands(pool, dtype, common.jax_key(run.seed)))
    evals0 = rt.stats.model_evals
    st["outputs"] = [None] * len(pool)
    for i, (op, _) in enumerate(pool):          # decide and compile each
        st["outputs"][i] = jax.block_until_ready(
            run_op(op, st["operands"][i], backend="pallas", runtime=rt))
        if run.trace:                           # the default-knob program
            jax.block_until_ready(run_op(op, st["operands"][i],
                                         backend="pallas",
                                         knob=default_knob(op)))
    run.counters["setup_model_evals"] = rt.stats.model_evals - evals0


def _call(run, i, knob=None):
    from repro.kernels.ops import run_op
    st = run.state
    op = st["pool"][i][0]
    return run_op(op, st["operands"][i], backend="pallas",
                  runtime=st["rt"], knob=knob)


def window(run, seconds: float) -> None:
    import jax
    st, pool = run.state, run.state["pool"]
    rng = np.random.default_rng(run.seed)
    itemsize = st["dtype"].itemsize
    evals0 = st["rt"].stats.model_evals
    work = [yardstick.l3_flops(*shape) for shape in pool]
    flops = dispatch = 0.0
    calls = failed = 0
    order = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    done = t0
    with run.annotate("bench.window"):
        while done < t_end:
            for i in rng.permutation(len(pool)):
                t_in = time.perf_counter()
                try:
                    out = _call(run, i)
                    t_ret = time.perf_counter()
                    out = run.fault("answer", out.block_until_ready())
                except Exception as e:          # counted, never hidden
                    failed += 1
                    run.log(f"[window] call {calls} {pool[i]} failed: {e!r}")
                    t_ret = time.perf_counter()
                else:
                    st["outputs"][i] = out
                    flops += work[i]
                done = time.perf_counter()
                dispatch += t_ret - t_in
                calls += 1
                order.append(int(i))
                if done >= t_end:
                    break
    win = done - t0
    run.counters["window_model_evals"] = st["rt"].stats.model_evals - evals0
    run.attempted, run.failed = calls, failed
    run.window_s = win
    run.metrics["blas_tflops"] = flops / win / 1e12
    run.raw.update(
        calls=calls, flops=flops, dispatch_s=dispatch,
        roofline_s=sum(yardstick.roofline_seconds(
            work[i], yardstick.l3_bytes(*pool[i], itemsize), run.peak)
            for i in order))
    if run.trace:
        # the same sequence, one round each, at the tuned and default knob
        from repro.kernels.ops import default_knob
        seq = range(len(pool))
        for tag, knob_of in (("bench.tuned", lambda op: None),
                             ("bench.default", default_knob)):
            with run.annotate(tag):
                for i in seq:
                    jax.block_until_ready(_call(run, i,
                                                knob_of(pool[i][0])))


def check(run) -> list:
    """Sampled shapes (``check_per_op`` per op, drawn from the seed): the
    window's last answer for each against the float64 reference."""
    import jax
    st, pool = run.state, run.state["pool"]
    per_op = run.cell["traffic_data"]["check_per_op"]
    rng = np.random.default_rng([run.seed, 1])
    picks = []
    for op in dict.fromkeys(op for op, _ in pool):
        idx = [i for i, (o, _) in enumerate(pool) if o == op]
        picks += sorted(rng.choice(idx, size=min(per_op, len(idx)),
                                   replace=False).tolist())
    worst, where = 0.0, None
    for i in picks:
        op, dims = pool[i]
        host = [np.asarray(jax.device_get(x)) for x in st["operands"][i]]
        got = (ref.control(op, host) if run.control
               else np.asarray(jax.device_get(st["outputs"][i])))
        err = ref.rel_err(got, ref.oracle(op, host))
        run.log(f"[check] {op} {dims}: max_rel_err={err:.3e}")
        if not err <= worst:
            worst, where = err, (op, dims)
    run.log(f"[check] worst {where}")
    st.clear()
    return [("max_rel_err", worst)]
