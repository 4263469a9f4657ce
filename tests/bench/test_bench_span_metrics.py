"""The per-layer readers of the program's own spans (``decision_us.blas``,
``dispatch_idle_us.blas``, ``launch_idle_us.decode``) on small hand-built
traces, against hand counts; and ``None`` on a trace that holds none of the
spans, as a program without them gives."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, tracing  # noqa: E402

READERS = ("decision_us.blas", "dispatch_idle_us.blas",
           "launch_idle_us.decode")


def _run(ops, spans):
    op = tracing.Op
    return SimpleNamespace(tr=tracing.Trace(
        ops=[op(d, "%fusion.1 = f32[8] fusion(x)", s, e) for d, s, e in ops],
        devices=2, spans=[("bench.window", 0, 1000)] + spans))


def _read(name, run):
    return common.metric_reader(name)(run)


def _blas_run():
    # device 0 busy [100, 300], [500, 600], [800, 900]; device 1's op and
    # everything outside [0, 1000] count for nothing
    ops = [(0, 100, 300), (0, 500, 600), (0, 800, 900), (1, 0, 1000),
           (0, 1000, 1200)]
    spans = [("blas.run_op", -50, 20),              # starts before the window
             ("blas.run_op", 50, 250), ("adsala.select", 60, 80),
             ("blas.launch", 90, 240),
             ("blas.run_op", 400, 650), ("adsala.select", 410, 430),
             ("blas.run_op", 950, 1100), ("adsala.select", 960, 970),
             ("adsala.select", 1200, 1210)]         # after the window
    return _run(ops, spans)


def test_decision_us_is_select_time_per_call():
    # (20 + 20 + 10) ns over 3 calls
    assert _read("decision_us.blas", _blas_run()) == pytest.approx(
        50 / 3 / 1e3)


def test_dispatch_idle_us_is_idle_inside_run_op_per_call():
    # idle inside [50, 250]: [50, 100]; inside [400, 650]: [400, 500] and
    # [600, 650]; inside [950, 1000] (clipped): all of it
    assert _read("dispatch_idle_us.blas", _blas_run()) == pytest.approx(
        (50 + 100 + 50 + 50) / 3 / 1e3)


def test_launch_idle_us_is_idle_inside_step_and_sample_per_step():
    ops = [(0, 150, 280), (0, 650, 900)]
    spans = [("serve.decode_step", 100, 200), ("serve.sample", 250, 300),
             ("serve.decode_step", 600, 700), ("serve.sample", 750, 800),
             ("np.asarray", 300, 600)]              # the read-back: outside
    # idle [100, 150] + [280, 300] + [600, 650], over 2 steps
    assert _read("launch_idle_us.decode", _run(ops, spans)) == \
        pytest.approx((50 + 20 + 50) / 2 / 1e3)


def test_nested_spans_count_their_idle_time_once():
    ops = [(0, 0, 100)]
    spans = [("serve.decode_step", 100, 300), ("serve.sample", 150, 250)]
    assert _read("launch_idle_us.decode", _run(ops, spans)) == \
        pytest.approx(200 / 1e3)


@pytest.mark.parametrize("name", READERS)
def test_no_span_reads_none(name):
    run = _run([(0, 100, 300)], [("ReadSyncFlag", 300, 900)])
    assert _read(name, run) is None
