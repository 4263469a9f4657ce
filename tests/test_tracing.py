"""The program's profiler spans, on the CPU.

``run_op``, the knob decision, the kernel launch and the serving session
write host spans with ``jax.profiler.TraceAnnotation``.  Under a profiler
session they land in the trace that ``bench/tracing.py`` reads, with plain
names (metadata in the event's stats); without one they are inert and the
calls return what they return with one.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import AdsalaRuntime
from repro.core.knobs import Knob
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

KNOB = Knob((("bm", 128), ("bk", 128), ("bn", 128)))
DIMS = (256, 192, 128)


class StubGemm:
    """A pallas gemm model that always picks ``KNOB`` and counts its
    evaluations."""
    backend, op, dtype_bytes, artifact_version = "pallas", "gemm", 4, 0

    def __init__(self):
        self.evals = 0

    def select(self, dims):
        self.evals += 1
        return KNOB


def _operands():
    m, k, n = DIMS
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    return (jax.random.normal(ka, (m, k), jnp.float32),
            jax.random.normal(kb, (k, n), jnp.float32))


def _gemm(rt, operands):
    return ops.run_op("gemm", operands, backend="pallas", runtime=rt,
                      interpret=True).block_until_ready()


def _xplane(path: Path) -> ProfileData:
    return ProfileData.from_file(str(sorted(path.rglob("*.xplane.pb"))[-1]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two calls with a fresh runtime under one profiler session, the
    kernel compiled beforehand: the first decision misses, the second
    hits."""
    operands = _operands()
    ops.run_op("gemm", operands, backend="pallas", knob=KNOB,
               interpret=True).block_until_ready()
    rt = AdsalaRuntime()
    sub = StubGemm()
    rt.register(sub)
    path = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(path)):
        outs = [_gemm(rt, operands), _gemm(rt, operands)]
    return {"path": path, "tr": tracing.load(path), "outs": outs,
            "evals": sub.evals, "operands": operands}


def _named(tr, name):
    return sorted((s, e) for n, s, e in tr.spans if n == name)


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_run_op_holds_the_decision_and_the_launch(traced):
    tr = traced["tr"]
    calls = _named(tr, "blas.run_op")
    assert len(calls) == 2
    for name in ("adsala.select", "blas.launch"):
        spans = _named(tr, name)
        assert len(spans) == 2
        assert all(_within(s, c) for s, c in zip(spans, calls))
    # the decision comes before the launch within each call
    for sel, launch in zip(_named(tr, "adsala.select"),
                           _named(tr, "blas.launch")):
        assert sel[1] <= launch[0]


def test_model_eval_only_on_the_miss(traced):
    tr = traced["tr"]
    first, second = _named(tr, "blas.run_op")
    evals = _named(tr, "adsala.model_eval")
    assert traced["evals"] == 1
    assert len(evals) == 1
    assert _within(evals[0], first)
    assert _within(evals[0], _named(tr, "adsala.select")[0])
    assert not _within(evals[0], second)


def test_span_names_are_plain_and_metadata_is_in_the_stats(traced):
    events = [ev for plane in _xplane(traced["path"]).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith(("blas.", "adsala."))]
    assert {ev.name for ev in events} == {"blas.run_op", "adsala.select",
                                          "blas.launch", "adsala.model_eval"}
    calls = [{k: v for k, v in ev.stats} for ev in events
             if ev.name == "blas.run_op"]
    assert calls == [{"op": "gemm", "dims": str(DIMS)}] * 2


def test_without_a_session_the_same_calls_return_the_same_results(traced):
    assert not TraceAnnotation.is_enabled()
    rt = AdsalaRuntime()
    rt.register(StubGemm())
    operands = traced["operands"]
    outs = [_gemm(rt, operands), _gemm(rt, operands)]
    for got, traced_out in zip(outs, traced["outs"]):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(traced_out))
    want = np.asarray(operands[0], np.float64) @ np.asarray(operands[1],
                                                            np.float64)
    np.testing.assert_allclose(np.asarray(outs[0]), want, rtol=1e-4,
                               atol=1e-3)
    assert rt.stats.model_evals == 1


@pytest.fixture(scope="module")
def session():
    from repro.configs import get_smoke_config
    from repro.launch.serve import ServeSession, init_serving_params
    cfg = get_smoke_config("qwen1.5-4b")
    return ServeSession(cfg=cfg, params=init_serving_params(cfg), max_len=16)


def _prompts(sess):
    return np.random.default_rng(3).integers(0, sess.cfg.vocab, (2, 6),
                                             dtype=np.int32)


def test_session_steps_keep_lower(session):
    from repro.models import init_decode_state
    prompts = _prompts(session)
    caches = init_decode_state(session.cfg, 2, session.max_len,
                               dtype=jnp.dtype(session.cfg.compute_dtype))
    tok = jnp.zeros((2, 1), jnp.int32)
    assert "module" in session._prefill.lower(
        session.params, {"tokens": jnp.asarray(prompts)}, caches).as_text()
    assert "module" in session._decode.lower(session.params, tok, caches,
                                             None).as_text()


def test_session_writes_its_spans(session, tmp_path):
    prompts = _prompts(session)
    plain = session.generate(prompts, max_new=3)        # compiles
    with jax.profiler.trace(str(tmp_path)):
        traced = session.generate(prompts, max_new=3)
    np.testing.assert_array_equal(plain, traced)
    tr = tracing.load(tmp_path)
    (prefill,) = _named(tr, "serve.prefill")
    for child in ("serve.init_cache", "serve.prefill_step"):
        (span,) = _named(tr, child)
        assert _within(span, prefill)
    assert len(_named(tr, "serve.decode_step")) == 3
    assert len(_named(tr, "serve.sample")) == 4
    assert all(s >= prefill[1] for s, _ in _named(tr, "serve.decode_step"))
