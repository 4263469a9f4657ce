"""The zero-copy execution contract (PR 5).

Three properties, each asserted at its own level:

  * **numerics** — masked edge-tile kernels are bit-identical to the old
    zero-pad + slice-back path on ragged shapes (the masked zeros occupy
    exactly the lanes the padding filled), and the native leading-batch
    grid is bit-identical to per-item execution;
  * **structure** — the traced dispatch path contains no pad/slice
    primitives, the ``beta == 0`` call takes no C operand at all, and the
    ``tri_packed`` variant launches exactly the n(n+1)/2 packed grid;
  * **knob space** — ``tri_packed`` is a first-class candidate that
    calibration can produce and legacy persisted artifacts keep selecting
    from their own (smaller) persisted spaces.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.knobs import Knob
from repro.kernels import ops
from repro.kernels.cpu_blocked import make_operands
from repro.kernels.gemm import gemm_pallas
from repro.kernels.introspect import (copy_op_counts, full_grid_for,
                                      packed_grid_for, pallas_grids)
from repro.kernels.syrk import detri, tri_count

TRI_OPS = ("syrk", "syr2k", "trmm")

RAGGED = {"gemm": (129, 65, 257), "symm": (129, 257), "syrk": (129, 65),
          "syr2k": (129, 65), "trmm": (129, 257), "trsm": (129, 257)}


def _knob(variant="full", bm=128, bk=128, bn=128):
    return Knob(tuple(sorted({"bm": bm, "bk": bk, "bn": bn,
                              "variant": variant}.items())))


def _jops(op, dims, seed=0):
    return tuple(jnp.asarray(x)
                 for x in make_operands(op, dims, np.float32, seed=seed))


def _padded_run(op, operands, variant="full"):
    """The frozen pre-PR-5 dispatch (ONE copy, shared with the CI smoke
    gate): zero-pad to block multiples (identity-pad the TRSM diagonal),
    run aligned, slice back."""
    from repro.kernels.padded_ref import padded_run
    return padded_run(op, operands, variant=variant, interpret=True)


# ---------------------------------------------------------------------------
# numerics: masked == padded, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ("gemm", "symm", "syrk", "syr2k", "trmm"))
@pytest.mark.parametrize("dims_key", ("ragged", "one-row"))
def test_masked_bitmatches_padded(op, dims_key):
    dims = RAGGED[op] if dims_key == "ragged" else \
        {"gemm": (1, 300, 384)}.get(op, (1, 384))
    operands = _jops(op, dims, seed=5)
    got = np.asarray(ops.run_op(op, operands, knob=_knob(), interpret=True))
    want = np.asarray(_padded_run(op, operands))
    assert np.array_equal(got, want), (op, dims)


def test_trsm_masked_matches_padded():
    """TRSM solves the ragged diagonal block at its true size instead of
    identity-padding it, so only the solve's low bits may move."""
    operands = _jops("trsm", RAGGED["trsm"], seed=5)
    got = np.asarray(ops.run_op("trsm", operands, knob=_knob(),
                                interpret=True))
    want = np.asarray(_padded_run("trsm", operands))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", TRI_OPS)
@pytest.mark.parametrize("dims", [(256, 128), (129, 65), (300, 300)])
def test_tri_packed_bitmatches_tri(op, dims):
    """The packed grid computes the identical per-block dot sequence — only
    the launch structure changes, so results are bit-identical."""
    operands = _jops(op, dims if op != "trmm" else (dims[0], dims[1]),
                     seed=9)
    tri = np.asarray(ops.run_op(op, operands, knob=_knob("tri"),
                                interpret=True))
    packed = np.asarray(ops.run_op(op, operands, knob=_knob("tri_packed"),
                                   interpret=True))
    assert np.array_equal(packed, tri), (op, dims)


@pytest.mark.parametrize("op", ("syrk", "syr2k"))
def test_tri_packed_beta_matches_tri(op):
    operands = _jops(op, (129, 65), seed=13)
    n = operands[0].shape[0]
    c = jnp.asarray(np.random.default_rng(1).standard_normal((n, n)),
                    jnp.float32)
    kw = dict(alpha=1.5, beta=0.5, interpret=True)
    tri = np.asarray(ops.run_op(op, operands + (c,), knob=_knob("tri"), **kw))
    packed = np.asarray(ops.run_op(op, operands + (c,),
                                   knob=_knob("tri_packed"), **kw))
    assert np.array_equal(packed, tri)


# ---------------------------------------------------------------------------
# numerics: native stacked batching == per-item execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ("gemm", "symm", "syrk", "syr2k", "trmm"))
def test_stacked_bitmatches_per_item(op):
    B = 3
    items = [_jops(op, RAGGED[op], seed=i) for i in range(B)]
    stacked = tuple(jnp.stack([it[i] for it in items])
                    for i in range(len(items[0])))
    knob = _knob("tri_packed" if op in TRI_OPS else "full")
    got = np.asarray(ops.run_op(op, stacked, knob=knob, stacked=True,
                                interpret=True))
    want = np.stack([np.asarray(ops.run_op(op, it, knob=knob,
                                           interpret=True))
                     for it in items])
    assert np.array_equal(got, want), op


def test_stacked_trsm_matches_per_item():
    B = 3
    items = [_jops("trsm", RAGGED["trsm"], seed=i) for i in range(B)]
    stacked = tuple(jnp.stack([it[i] for it in items]) for i in range(2))
    got = np.asarray(ops.run_op("trsm", stacked, knob=_knob(), stacked=True,
                                interpret=True))
    want = np.stack([np.asarray(ops.run_op("trsm", it, knob=_knob(),
                                           interpret=True))
                     for it in items])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_stacked_is_one_native_grid():
    """The stack executes as ONE pallas_call whose leading grid dim is the
    batch width (not a vmap batching-rule artifact)."""
    B = 4
    a = jnp.ones((B, 129, 65), jnp.float32)
    b = jnp.ones((B, 65, 257), jnp.float32)
    grids = pallas_grids(ops.gemm, a, b, knob=_knob(), interpret=True)
    assert len(grids) == 1
    assert grids[0] == (B, 2, 3, 1)      # (B, ⌈m/bm⌉, ⌈n/bn⌉, ⌈k/bk⌉)


# ---------------------------------------------------------------------------
# structure: the zero-copy jaxpr contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ("gemm", "symm", "syrk", "syr2k", "trmm"))
def test_no_pad_or_slice_in_dispatch(op):
    operands = _jops(op, RAGGED[op], seed=3)
    knob = _knob("tri_packed" if op in TRI_OPS else "full")
    counts = copy_op_counts(ops.PALLAS_OPS[op], *operands, knob=knob,
                            interpret=True)
    assert counts == {}, (op, counts)


@pytest.mark.parametrize("variant", ("full", "tri"))
@pytest.mark.parametrize("op", TRI_OPS)
def test_no_pad_or_slice_in_unpacked_variants(op, variant):
    """The rectangular-grid variants of the triangular ops are zero-copy
    too: no host-side pad or slice at a ragged shape."""
    operands = _jops(op, RAGGED[op], seed=3)
    counts = copy_op_counts(ops.PALLAS_OPS[op], *operands,
                            knob=_knob(variant), interpret=True)
    assert counts == {}, (op, variant, counts)


def test_trsm_has_no_pad():
    """TRSM's substitution loop slices block rows (that's the algorithm)
    but never pads an operand — the identity-padded diagonal is gone."""
    operands = _jops("trsm", RAGGED["trsm"], seed=3)
    counts = copy_op_counts(ops.PALLAS_OPS["trsm"], *operands, knob=_knob(),
                            interpret=True)
    assert counts.get("pad", 0) == 0, counts


def test_beta_zero_takes_no_c_operand():
    """``beta == 0`` must not materialize (or DMA) a zeros C operand."""
    import jax
    a = jnp.ones((129, 65), jnp.float32)
    b = jnp.ones((65, 257), jnp.float32)

    def n_pallas_inputs(fn, *args, **kw):
        found = []

        def walk(jx):
            for e in jx.eqns:
                if e.primitive.name == "pallas_call":
                    found.append(len(e.invars))
                    continue
                for v in e.params.values():
                    if hasattr(v, "jaxpr"):
                        walk(v.jaxpr)
        walk(jax.make_jaxpr(lambda *xs: fn(*xs, **kw))(*args).jaxpr)
        return found

    assert n_pallas_inputs(
        lambda x, y: gemm_pallas(x, y, interpret=True), a, b) == [2]
    c = jnp.ones((129, 257), jnp.float32)
    assert n_pallas_inputs(
        lambda x, y, z: gemm_pallas(x, y, z, beta=0.5, interpret=True),
        a, b, c) == [3]
    # beta == 0 with a C present: C is still dead — not an input
    assert n_pallas_inputs(
        lambda x, y, z: gemm_pallas(x, y, z, beta=0.0, interpret=True),
        a, b, c) == [2]


@pytest.mark.parametrize("op", TRI_OPS)
def test_packed_grid_is_exactly_triangular(op):
    """tri_packed launches n(n+1)/2 packed blocks — times (k-steps + the
    write-only mirror step) for the rank-k ops, times the n-blocks for
    trmm — vs the full n²-block grid."""
    dims = (1024, 512) if op in ("syrk", "syr2k") else (1024, 512)
    operands = _jops(op, dims, seed=1)
    for variant, want in (
            ("full", full_grid_for(op, dims, 128, 128, 128)),
            ("tri", full_grid_for(op, dims, 128, 128, 128)),
            ("tri_packed", packed_grid_for(op, dims, 128, 128, 128))):
        grids = pallas_grids(ops.PALLAS_OPS[op], *operands,
                             knob=_knob(variant), interpret=True)
        assert grids == [want], (op, variant, grids)
    nb = -(-dims[0] // 128)
    packed = packed_grid_for(op, dims, 128, 128, 128)
    assert tri_count(nb) in packed       # n(n+1)/2 really is a grid dim


#: full-grid slots over tri_packed slots at 128-blocks, to 3 places:
#: 16²·16 over 136·(16 + 1) for the rank-k updates, 16·16·8 over 8·136
#: for trmm
PACKED_SLOT_RATIO = {"syrk": ((2048, 2048), 1.772),
                     "syr2k": ((2048, 2048), 1.772),
                     "trmm": ((2048, 1024), 1.882)}


@pytest.mark.parametrize("op", TRI_OPS)
def test_packed_slot_saving_at_2048(op):
    """The packed grid's saving in launched grid slots over the full grid,
    at n = 2048 (traced, not executed)."""
    dims, want = PACKED_SLOT_RATIO[op]
    operands = _jops(op, dims)
    (full,) = pallas_grids(ops.PALLAS_OPS[op], *operands, knob=_knob(),
                           interpret=True)
    (packed,) = pallas_grids(ops.PALLAS_OPS[op], *operands,
                             knob=_knob("tri_packed"), interpret=True)
    assert round(math.prod(full) / math.prod(packed), 3) == want


def test_detri_is_exact():
    t = jnp.arange(tri_count(64))
    i, j = detri(t)
    i, j = np.asarray(i), np.asarray(j)
    want_i = np.repeat(np.arange(64), np.arange(1, 65))
    want_j = np.concatenate([np.arange(r + 1) for r in range(64)])
    assert np.array_equal(i, want_i) and np.array_equal(j, want_j)


# ---------------------------------------------------------------------------
# knob space: tri_packed is a first-class candidate
# ---------------------------------------------------------------------------

def test_knob_space_exposes_tri_packed():
    for op in TRI_OPS:
        space = ops.knob_space_for(op)
        variants = {c.dict["variant"] for c in space.candidates}
        assert variants == {"full", "tri", "tri_packed"}, op
    # gemm/symm/trsm spaces unchanged
    assert {c.dict["variant"] for c in ops.knob_space_for("gemm")} == \
        {"full"}
    # the baseline (max-parallelism) knob stays the full variant — legacy
    # defaults and decision caches keep meaning what they meant
    for op in TRI_OPS:
        assert ops.default_knob(op).dict["variant"] == "full"


def test_tri_packed_is_feature_distinguishable():
    """The parallelism feature (the paper's nt analogue, the only
    knob-dependent feature channel) must separate tri_packed from full —
    otherwise their Table-III rows are byte-identical and no model could
    ever learn to select the packed variant.  full and tri launch the same
    grid, so those two deliberately share a row (and tie toward full)."""
    for op in TRI_OPS:
        space = ops.knob_space_for(op)
        by_var = {}
        for c in space.candidates:
            d = c.dict
            if d["bm"] == 128 and d["bn"] == 128:
                by_var[d["variant"]] = space.parallelism(c, (2048, 512))
        assert by_var["full"] == by_var["tri"]
        assert by_var["tri_packed"] < by_var["full"]
        cm, cn = 2048 // 128, 512 // 128
        assert by_var["tri_packed"] == (cm + 1) * cn / 2.0
    # degenerate single-block-row shapes tie (nothing to pack)
    space = ops.knob_space_for("syrk")
    for c in space.candidates:
        if c.dict["bm"] == 128 and c.dict["bn"] == 128:
            assert space.parallelism(c, (64, 128)) == 1.0


def test_tri_packed_knob_executes_everywhere():
    """The enlarged candidate set must be *executable* by every backend
    that shares the knob space (calibration sweeps all candidates)."""
    from repro.kernels.cpu_blocked import run_blocked
    for op in TRI_OPS:
        dims = (129, 65)
        operands = make_operands(op, dims, np.float32, seed=2)
        knob = _knob("tri_packed", bm=64, bk=64, bn=64)
        got = run_blocked(op, operands, knob)
        want = run_blocked(op, operands, _knob("full", bm=64, bk=64, bn=64))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_fresh_calibration_covers_tri_packed(tmp_path):
    """A fresh install over the enlarged space produces an artifact whose
    model scores tri_packed candidates — and whose selection executes."""
    from repro.backends import get_backend
    from repro.core import AdsalaRuntime, ModelRegistry, install_backend
    registry = ModelRegistry(tmp_path)
    rt = AdsalaRuntime()
    subs = install_backend(get_backend("cpu_blocked"), ops=("syrk",),
                           n_samples=10, dim_lo=32, dim_hi=128,
                           max_footprint_bytes=1_000_000, tune_trials=1,
                           candidates=("DecisionTree",), runtime=rt,
                           registry=registry, seed=0)
    sub = subs["syrk"]
    variants = {c.dict["variant"] for c in sub.knob_space.candidates}
    assert "tri_packed" in variants
    knob = rt.select("syrk", (96, 64), 4, backend="cpu_blocked")
    assert knob in sub.knob_space.candidates
    # whatever it picked executes correctly (including tri_packed)
    operands = make_operands("syrk", (96, 64), np.float32, seed=3)
    from repro.kernels.cpu_blocked import run_blocked
    got = run_blocked("syrk", operands, knob)
    want = operands[0] @ operands[0].T
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_legacy_artifacts_still_select():
    """Persisted pre-PR-5 artifacts carry their own (smaller) knob spaces;
    they must keep loading and selecting valid, executable knobs."""
    from repro.core import AdsalaRuntime, ModelRegistry
    reg = ModelRegistry("runs/adsala/models")
    rt = AdsalaRuntime()
    if reg.load_into(rt, backend="cpu_blocked") == 0:
        pytest.skip("no persisted artifacts in the repo checkout")
    for op in ("syrk", "trmm"):
        knob = rt.select(op, (160, 96), 4, backend="cpu_blocked")
        d = knob.dict
        assert d["variant"] in ("full", "tri", "tri_packed")
        operands = make_operands(op, (160, 96), np.float32, seed=4)
        from repro.kernels.cpu_blocked import run_blocked
        got = run_blocked(op, operands, knob)
        want = operands[0] @ operands[0].T if op == "syrk" \
            else np.tril(operands[0]) @ operands[1]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
