#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, through the entry points users call.

    python3 chip_smoke.py             # one chip: phases A-E
    python3 chip_smoke.py --chips 4   # four chips: sharded qwen1.5-4b vs one

One process holds the chip(s) throughout.  Phases, in order:

  A  device   JAX must report a TPU; there is no CPU fallback.
  B  kernels  the six Level-3 ops through ``run_op(backend="pallas")``,
              compiled, in f32 and bf16, at a square and a ragged shape, in
              every kernel variant, with the default and a 512-block knob;
              each result against the float64 oracle of
              ``repro.backends.conformance`` at its per-dtype tolerance.
  C  install  gemm f32 calibrated with the pallas wall-clock timer into a
              fresh directory, loaded into an ``AdsalaRuntime``, and used to
              select and execute at dims the sweep did not sample.
  D  serving  a ``BlasService`` on that runtime answers gemm and syrk
              requests; any fallback, retry, quarantine, resolve fallback,
              predictor failure or artifact load error fails the smoke.
  E  model    qwen1.5-4b at its published width and depth (random bf16
              weights from a seed) served by ``ServeSession``; its prefill
              and decode programs must hold the compiled kernels, and the
              routed prefill's last-token logits are checked against the
              same weights run unrouted (XLA ``dot``).

With ``--chips 4`` only phase A and the sharded comparison run: qwen1.5-4b
on a data=1 x model=4 mesh against the same prompts on one chip, both
through XLA's ``dot``, so that only the sharding differs.

Times and memory are informational.  Any failed phase exits non-zero and
the result line is not printed; otherwise the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: square kernel size and one ragged shape per op (Table I free dims)
SQUARE = 2048
RAGGED = {"gemm": (2500, 1900, 1300), "other": (1900, 1300)}
VARIANT_OPS = ("syrk", "syr2k", "trmm")
VARIANTS = ("full", "tri", "tri_packed")

#: calibration sweep (phase C): the default knob space, a handful of samples
CAL_SAMPLES = 8
CAL_DIMS = (128, 2048)
CAL_FOOTPRINT_MB = 64.0
#: dims selected and executed after the install; checked as unsampled
UNSAMPLED = ((640, 1536, 896), (1152, 384, 1920), (1792, 1024, 256))

#: serving traffic (phase D): (op, dims, requests)
SERVE_MIX = (("gemm", (512, 512, 512), 10), ("gemm", (1024, 768, 512), 10),
             ("gemm", (384, 1024, 1536), 10), ("syrk", (1024, 512), 4))

#: model server (phase E and --chips 4)
ARCH = "qwen1.5-4b"
REQUESTS, PROMPT_LEN, MAX_NEW = 4, 128, 16
#: routed vs unrouted last-token logits (phase E): ||a - b||_2 / ||b||_2.
#: bf16 keeps 8 significant bits (u = 2**-8); the Pallas kernels and XLA's
#: dot round at different points in each of the 40 layers.
LOGITS_REL_TOL = 5e-2
#: sharded vs one-chip last-token logits (--chips 4), both through XLA's
#: dot, so only the partitioning differs: the partial sums of the sharded
#: contractions are rounded to bf16 before their all-reduce
SHARDED_REL_TOL = 3e-2
#: --chips 4: each device's share of the weight bytes
SHARE_TOL = 0.02


class SmokeError(AssertionError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def assert_compiled(hlo_text: str, what: str) -> None:
    check("tpu_custom_call" in hlo_text,
          f"{what}: no tpu_custom_call in the compiled program "
          f"(the kernel did not run compiled)")


def peak_memory_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# phase B: kernels
# ---------------------------------------------------------------------------

def kernel_dims(op: str, square: int, ragged: dict) -> list[tuple]:
    if op == "gemm":
        return [(square,) * 3, ragged["gemm"]]
    return [(square,) * 2, ragged["other"]]


def kernel_knobs(op: str) -> list:
    """The default knob and the 512-block knob, in every variant of ``op``."""
    from repro.kernels import ops
    space = ops.knob_space_for(op)
    default = ops.default_knob(op).dict
    variants = VARIANTS if op in VARIANT_OPS else (default["variant"],)
    out = []
    for blocks in ({k: default[k] for k in ("bm", "bk", "bn")},
                   {"bm": 512, "bk": 512, "bn": 512}):
        for v in variants:
            want = dict(blocks, variant=v)
            out.extend(k for k in space.candidates if k.dict == want)
    check(len(out) == 2 * len(variants), f"{op}: knobs {out}")
    return out


def phase_kernels(square: int = SQUARE, ragged: dict = RAGGED) -> None:
    import jax
    import jax.numpy as jnp
    from repro.backends import L3_OPS, conformance, get_backend
    from repro.kernels.ops import run_op

    be = get_backend("pallas")
    worst = 0.0
    bad = []
    for op in L3_OPS:
        knobs = kernel_knobs(op)
        for dtype in (jnp.float32, jnp.bfloat16):
            tol = conformance.tolerance_for(dtype)
            name = np.dtype(dtype).name
            for dims in kernel_dims(op, square, ragged):
                host = be.make_operands(op, dims, dtype, seed=sum(dims))
                want = conformance.oracle(op, host)
                operands = be.prepare(host)
                for knob in knobs:
                    fn = jax.jit(lambda *x, op=op, knob=knob: run_op(
                        op, x, backend="pallas", knob=knob))
                    t0 = time.perf_counter()
                    compiled = fn.lower(*operands).compile()
                    t_compile = time.perf_counter() - t0
                    assert_compiled(compiled.as_text(), f"{op} {knob}")
                    out = jax.block_until_ready(compiled(*operands))
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(*operands))
                    t_run = time.perf_counter() - t0
                    err = conformance.rel_err(out, want)
                    worst = max(worst, err / tol)
                    kd = knob.dict
                    print(f"[B] {op:5s} {name:8s} dims={dims} "
                          f"blocks={kd['bm']}/{kd['bk']}/{kd['bn']} "
                          f"variant={kd['variant']:10s} max_rel_err={err:.3e} "
                          f"tol={tol:.0e} compile_s={t_compile:.2f} "
                          f"run_ms={t_run * 1e3:.3f} (informational)",
                          flush=True)
                    if not err < tol:
                        bad.append(f"{op} {name} {dims} {knob}: "
                                   f"rel err {err:.3e} >= {tol:.0e}")
    check(not bad, "; ".join(bad))
    check(be.interpret is False, "pallas backend is in interpret mode")
    print(f"[B] interpret={be.interpret}; worst error/tolerance "
          f"{worst:.3f}", flush=True)


# ---------------------------------------------------------------------------
# phase C: install on the chip
# ---------------------------------------------------------------------------

def phase_install(out_dir: Path, *, samples: int = CAL_SAMPLES,
                  dims_range: tuple = CAL_DIMS,
                  footprint_mb: float = CAL_FOOTPRINT_MB,
                  unsampled: tuple = UNSAMPLED):
    """Returns (runtime, registry) for phase D."""
    import jax
    from repro.backends import conformance, get_backend
    from repro.core import AdsalaRuntime, ModelRegistry
    from repro.kernels.ops import run_op
    from repro.launch.calibrate import calibrate_one

    report = calibrate_one(
        "gemm", "s", out_dir, backend="pallas", samples=samples,
        dim_lo=dims_range[0], dim_hi=dims_range[1],
        footprint_mb=footprint_mb, sizes=(128, 256, 512), tune_trials=1,
        seed=0, candidates=("LinearRegression", "DecisionTree"),
        log=lambda m: print(f"[C] {m.strip()}", flush=True))
    print(f"[C] model={report['best_model']} samples={report['n_samples']} "
          f"knobs={report['n_knobs']} "
          f"gather_s={report['gather_seconds']} (informational)", flush=True)
    for row in report["models"]:
        print(f"[C]   candidate {row}", flush=True)

    reg = ModelRegistry(out_dir / "models")
    rt = AdsalaRuntime()
    loaded = reg.load_into(rt)
    check(loaded == 1 and not reg.last_load_errors,
          f"loaded {loaded} artifacts, errors {reg.last_load_errors}")
    check(rt.has("gemm", 4, "pallas"), "no pallas gemm f32 model loaded")
    with np.load(out_dir / "datasets" / "pallas__gemm_s.npz") as ds:
        sampled = {tuple(int(v) for v in d) for d in ds["dims"]}
    be = get_backend("pallas")
    tol = conformance.tolerance_for(np.float32)
    for dims in unsampled:
        check(dims not in sampled, f"{dims} was sampled by the sweep")
        knob = rt.select("gemm", dims, 4, backend="pallas")
        host = be.make_operands("gemm", dims, np.float32, seed=sum(dims))
        out = jax.block_until_ready(
            run_op("gemm", be.prepare(host), backend="pallas", runtime=rt))
        err = conformance.rel_err(out, conformance.oracle("gemm", host))
        print(f"[C] select dims={dims} -> {knob} max_rel_err={err:.3e}",
              flush=True)
        check(err < tol, f"gemm {dims}: rel err {err:.3e} >= {tol:.0e}")
    s = rt.stats
    print(f"[C] runtime model_evals={s.model_evals} "
          f"eval_failures={s.eval_failures}", flush=True)
    check(s.model_evals >= len(unsampled) and s.eval_failures == 0,
          f"decisions did not come from the model: {s}")
    return rt, reg


# ---------------------------------------------------------------------------
# phase D: BLAS serving
# ---------------------------------------------------------------------------

def phase_serving(rt, reg, mix: tuple = SERVE_MIX) -> None:
    from repro.backends import conformance, get_backend
    from repro.serving import BlasService, ServeConfig

    be = get_backend("pallas")
    tol = conformance.tolerance_for(np.float32)
    work = []
    for op, dims, n in mix:
        for i in range(n):
            host = be.make_operands(op, dims, np.float32,
                                    seed=1000 * i + sum(dims))
            work.append((op, dims, host, conformance.oracle(op, host)))
    t0 = time.perf_counter()
    with BlasService(runtime=rt, config=ServeConfig(backend="pallas")) as svc:
        futs = [svc.submit(op, host) for op, _, host, _ in work]
        outs = [f.result(timeout=600) for f in futs]
        stats = svc.stats
    wall = time.perf_counter() - t0
    worst = 0.0
    for (op, dims, _, want), out in zip(work, outs):
        err = conformance.rel_err(out, want)
        worst = max(worst, err)
        check(err < tol, f"served {op} {dims}: rel err {err:.3e}")
    rs = rt.stats
    print(f"[D] served={stats.completed} failed={stats.failed} "
          f"batches={stats.batches} max_rel_err={worst:.3e} "
          f"wall_s={wall:.2f} (informational, includes compiles)",
          flush=True)
    print(f"[D] fallback_executions={stats.fallback_executions} "
          f"retries={stats.retries} "
          f"quarantined_knobs={stats.quarantined_knobs} "
          f"resolve_fallbacks={dict(rs.resolve_fallbacks)} "
          f"eval_failures={rs.eval_failures} "
          f"last_load_errors={reg.last_load_errors}", flush=True)
    check(stats.completed == len(work) and stats.failed == 0,
          "not every request was answered")
    check(stats.fallback_executions == stats.retries
          == stats.quarantined_knobs == 0, "the serving ladder was walked")
    check(not rs.resolve_fallbacks, "a backend resolution fell back")
    check(rs.eval_failures == 0, "a predictor evaluation failed")
    check(not reg.last_load_errors, "an artifact failed to load")


# ---------------------------------------------------------------------------
# phase E and --chips 4: the model server
# ---------------------------------------------------------------------------

def model_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), param_dtype="bfloat16",
                               use_pallas_gemm=True)


def prompts_for(cfg) -> np.ndarray:
    rng = np.random.default_rng(0)
    return rng.integers(0, cfg.vocab, size=(REQUESTS, PROMPT_LEN),
                        dtype=np.int32)


def timed_generate(sess, prompts):
    """(last-token logits, tokens, prefill s, decode s/step), warm."""
    import jax
    logits = jax.block_until_ready(sess.prefill(prompts)[0])   # compile
    sess.generate(prompts, max_new=MAX_NEW)                     # compile
    t0 = time.perf_counter()
    logits = jax.block_until_ready(sess.prefill(prompts)[0])
    t_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    tokens = sess.generate(prompts, max_new=MAX_NEW)
    t_gen = time.perf_counter() - t0
    return (np.asarray(logits, np.float32), tokens, t_prefill,
            max(t_gen - t_prefill, 0.0) / MAX_NEW)


def assert_routed(sess, prompts) -> None:
    """The routed prefill and decode programs hold the compiled kernels."""
    import jax.numpy as jnp
    from repro.models import init_decode_state
    cfg = sess.cfg
    caches = init_decode_state(cfg, prompts.shape[0], sess.max_len,
                               dtype=jnp.dtype(cfg.compute_dtype))
    tok = jnp.zeros((prompts.shape[0], 1), jnp.int32)
    assert_compiled(sess._prefill.lower(
        sess.params, {"tokens": jnp.asarray(prompts)}, caches).as_text(),
        "routed prefill")
    assert_compiled(sess._decode.lower(sess.params, tok, caches,
                                       None).as_text(), "routed decode")


def compare_logits(tag: str, got, want, got_tok, want_tok, *,
                   tol: float) -> None:
    from repro.backends import conformance
    check(got.shape == want.shape == (REQUESTS, 1, model_config().vocab),
          f"{tag}: logits shape {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          f"{tag}: non-finite logits")
    want = want.astype(np.float64)
    rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    # least-squares scale of got onto want: a gap that is one common
    # factor reads as scale != 1 with a small remainder
    scale = float(np.vdot(got, want) / np.vdot(want, want))
    rest = float(np.linalg.norm(got - scale * want) / np.linalg.norm(want))
    agree = float(np.mean(got_tok == want_tok))
    first = float(np.mean(got_tok[:, 0] == want_tok[:, 0]))
    print(f"[{tag}] logits rel_l2={rel_l2:.3e} (tol {tol:.0e}) "
          f"rel_max={conformance.rel_err(got, want):.3e} "
          f"max_abs={np.max(np.abs(want)):.3f} scale={scale:.5f} "
          f"rel_l2_after_scale={rest:.3e}; greedy tokens agree "
          f"{agree:.3f} (first token {first:.3f})", flush=True)
    check(rel_l2 < tol, f"{tag}: logits rel_l2 {rel_l2:.3e}")


def phase_model() -> None:
    import jax
    from repro.launch.serve import ServeSession, init_serving_params

    cfg = model_config()
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_serving_params(cfg, seed=0))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[E] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} weights={n_bytes / 1e9:.3f} GB "
          f"bf16, init under jit {time.perf_counter() - t0:.1f}s", flush=True)
    max_len = PROMPT_LEN + MAX_NEW + 8
    prompts = prompts_for(cfg)
    routed = ServeSession(cfg=cfg, params=params, max_len=max_len)
    assert_routed(routed, prompts)
    logits, tokens, t_pre, t_dec = timed_generate(routed, prompts)
    print(f"[E] routed: {REQUESTS} requests x {PROMPT_LEN} prompt tokens, "
          f"{MAX_NEW} new; prefill_s={t_pre:.4f} decode_s_per_step="
          f"{t_dec:.5f} (informational)", flush=True)
    plain = ServeSession(cfg=dataclasses.replace(cfg, use_pallas_gemm=False),
                         params=params, max_len=max_len)
    ref_logits, ref_tokens, r_pre, r_dec = timed_generate(plain, prompts)
    print(f"[E] unrouted (XLA dot): prefill_s={r_pre:.4f} "
          f"decode_s_per_step={r_dec:.5f} (informational)", flush=True)
    compare_logits("E", logits, ref_logits, tokens, ref_tokens,
                   tol=LOGITS_REL_TOL)
    print(f"[E] peak device memory {peak_memory_gb(dev)} (informational)",
          flush=True)


def phase_sharded(n_chips: int) -> None:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ServeSession, init_serving_params
    from repro.launch.specs import rules_for

    # the sharded path keeps XLA's dot (GSPMD partitions it); the one-chip
    # reference is unrouted too, so the comparison isolates the sharding
    cfg = dataclasses.replace(model_config(), use_pallas_gemm=False)
    mesh = make_host_mesh(data=1, model=n_chips)
    rules = rules_for(mesh, "decode")
    params = jax.block_until_ready(
        init_serving_params(cfg, seed=0, mesh=mesh, rules=rules))
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per_dev[shard.device] += shard.data.nbytes
    shares = [per_dev[d] / total for d in mesh.devices.flat]
    print(f"[S] weights {total / 1e9:.3f} GB; share per device "
          f"{[round(s, 4) for s in shares]}", flush=True)
    check(all(abs(s - 1 / n_chips) <= SHARE_TOL for s in shares),
          f"weights are not spread over {n_chips} devices: {shares}")

    max_len = PROMPT_LEN + MAX_NEW + 8
    prompts = prompts_for(cfg)
    sharded = ServeSession(cfg=cfg, params=params, max_len=max_len,
                           mesh=mesh, rules=rules)
    logits, tokens, t_pre, t_dec = timed_generate(sharded, prompts)
    print(f"[S] sharded data=1 x model={n_chips}: prefill_s={t_pre:.4f} "
          f"decode_s_per_step={t_dec:.5f} (informational)", flush=True)
    one = jax.devices()[0]
    single = ServeSession(cfg=cfg, params=jax.device_put(params, one),
                          max_len=max_len)
    ref_logits, ref_tokens, r_pre, r_dec = timed_generate(single, prompts)
    print(f"[S] one chip (unrouted): prefill_s={r_pre:.4f} "
          f"decode_s_per_step={r_dec:.5f} (informational)", flush=True)
    compare_logits("S", logits, ref_logits, tokens, ref_tokens,
                   tol=SHARDED_REL_TOL)
    for d in mesh.devices.flat:
        print(f"[S] {d}: peak memory {peak_memory_gb(d)} (informational)",
              flush=True)


# ---------------------------------------------------------------------------

def run_phase(name: str, fn, failures: list):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception:            # noqa: BLE001 — report, then fail the run
        traceback.print_exc()
        failures.append(name)
        print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
              flush=True)
        return None
    print(f"[{name}] passed in {time.perf_counter() - t0:.1f}s", flush=True)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[A] JAX's backend is {platform!r}, not a TPU: nothing to "
              f"smoke-test here", file=sys.stderr)
        return 1
    print(f"[A] jax={jax.__version__} device_kind={devices[0].device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if len(devices) != args.chips:
        print(f"[A] --chips {args.chips} but JAX sees {len(devices)} "
              f"devices", file=sys.stderr)
        return 1

    failures: list[str] = []
    if args.chips > 1:
        run_phase("S", lambda: phase_sharded(args.chips), failures)
    else:
        run_phase("B", phase_kernels, failures)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
            installed = run_phase("C", lambda: phase_install(Path(td)),
                                  failures)
            if installed is not None:
                run_phase("D", lambda: phase_serving(*installed), failures)
            else:
                failures.append("D")
                print("[D] skipped: phase C failed", flush=True)
        run_phase("E", phase_model, failures)
    if failures:
        print(f"chip smoke FAILED: phases {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
