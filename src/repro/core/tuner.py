"""End-to-end install-time tuning pipeline for one BLAS L3 subroutine
(paper Fig. 1a):

    Halton sampling → timing sweep → Table-III features → LOF outlier removal
    → Yeo-Johnson + standardize + corr-prune → stratified split → per-model
    hyper-parameter tuning → estimated-speedup model selection → persist.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

from . import features as F
from .dataset import TimingDataset, gather
from .knobs import Knob, KnobSpace
from .lof import remove_outliers
from .ml import PAPER_CANDIDATES
from .preprocess import PreprocessPipeline
from .selection import ModelReport, evaluate_candidates, select_best
from .split import stratified_split

__all__ = ["TunedSubroutine", "install_subroutine", "install_backend"]

#: persisted artifact schema: v1 = single-backend (implicit pallas),
#: v2 = backend-tagged
SCHEMA_VERSION = 2


@dataclasses.dataclass
class TunedSubroutine:
    """The production artifact: everything runtime needs for one subroutine."""
    op: str
    dtype_bytes: int
    knob_space: KnobSpace
    pipeline: PreprocessPipeline
    model: object                       # fitted Estimator
    model_name: str
    log_target: bool
    reports: list[ModelReport] = dataclasses.field(default_factory=list)
    dataset: TimingDataset | None = None
    backend: str = "pallas"             # execution backend this was tuned on
    #: monotonically increasing per-artifact generation, stamped by
    #: :meth:`~repro.core.registry.ModelRegistry.save` (0 = never persisted
    #: through a registry / pre-versioning artifact).  The runtime persists
    #: it with every decision-cache entry so a warm restart can reject
    #: decisions made by a different generation of this model instead of
    #: silently replaying a predecessor's knobs.
    artifact_version: int = 0

    # -- runtime decision --------------------------------------------------
    def predict_times(self, dims: tuple[int, ...]) -> np.ndarray:
        """Predicted runtime for every knob candidate at these dims.

        This is the REFERENCE decision path: the runtime serves decisions
        through :meth:`compiled` (bit-identical argmin, far lower latency)
        and parity tests compare the two."""
        K = len(self.knob_space)
        X = F.build_features(self.op, np.tile(np.array(dims), (K, 1)),
                             self.knob_space.parallelism_vec(dims))
        pred = self.model.predict(self.pipeline.transform(X))
        return np.exp(pred) if self.log_target else pred

    def select(self, dims: tuple[int, ...]) -> Knob:
        return self.knob_space.candidates[int(np.argmin(self.predict_times(dims)))]

    def compiled(self):
        """The cached :class:`~repro.core.fastpath.CompiledPredictor` for
        this artifact (None when uncompilable)."""
        try:
            return self._compiled
        except AttributeError:
            from .fastpath import compile_predictor
            self._compiled = compile_predictor(self)
            return self._compiled

    # -- persistence ---------------------------------------------------------
    def get_state(self) -> dict:
        state = {
            "version": SCHEMA_VERSION,
            "backend": self.backend,
            "op": self.op,
            "dtype_bytes": self.dtype_bytes,
            "knobs": self.knob_space.get_state(),
            "pipeline": self.pipeline.get_state(),
            "model_name": self.model_name,
            "model": self.model.get_state(),
            "log_target": self.log_target,
            "reports": [r.row() for r in self.reports],
        }
        # optional key: ignored by older readers — no schema bump needed
        if self.artifact_version:
            state["artifact_version"] = int(self.artifact_version)
        return state


def install_subroutine(
    op: str,
    knob_space: KnobSpace,
    timer_fn: Callable[[tuple[int, ...], Knob], float],
    *,
    n_samples: int = 200,
    dim_lo: int = 16,
    dim_hi: int = 1024,
    max_footprint_bytes: int | None = 32 * 1024 * 1024,
    dtype_bytes: int = 4,
    candidates: Sequence[str] = PAPER_CANDIDATES,
    log_target: bool = True,
    use_lof: bool = True,
    use_yeo_johnson: bool = True,
    tune_trials: int = 6,
    test_frac: float = 0.15,
    seed: int = 0,
    dataset: TimingDataset | None = None,
    keep_dataset: bool = True,
    progress: Callable[[int, int], None] | None = None,
    backend: str = "pallas",
) -> TunedSubroutine:
    """Run the full ADSALA install for one subroutine; returns the artifact."""
    ds = dataset if dataset is not None else gather(
        op, knob_space, timer_fn, n_samples=n_samples, dim_lo=dim_lo,
        dim_hi=dim_hi, max_footprint_bytes=max_footprint_bytes,
        dtype_bytes=dtype_bytes, seed=seed, progress=progress)

    # stratify samples on their best measured time so slow/fast regimes are
    # represented in both splits (paper: stratified sampling, 15% test)
    best_t = ds.times.min(axis=1)
    train_s, test_s = stratified_split(np.log(np.maximum(best_t, 1e-12)),
                                       test_frac=test_frac, seed=seed)

    # LOF outlier removal on the flattened training rows (features ∪ label)
    lof_keep = None
    if use_lof:
        X_all, y_all, sample_idx = ds.flatten()
        in_train = np.isin(sample_idx, train_s)
        y_log = np.log(np.maximum(y_all, 1e-12))
        _, _, keep_sub = remove_outliers(X_all[in_train], y_log[in_train])
        lof_keep = np.ones(X_all.shape[0], dtype=bool)
        lof_keep[np.flatnonzero(in_train)] = keep_sub

    pipeline = PreprocessPipeline(use_yeo_johnson=use_yeo_johnson)
    reports = evaluate_candidates(
        ds, pipeline, train_s, test_s, candidates=candidates,
        log_target=log_target, tune_trials=tune_trials, seed=seed,
        lof_keep_mask=lof_keep)
    best = select_best(reports)
    sub = TunedSubroutine(
        op=op, dtype_bytes=dtype_bytes, knob_space=knob_space,
        pipeline=pipeline, model=best.model, model_name=best.name,
        log_target=log_target, reports=reports,
        dataset=ds if keep_dataset else None, backend=backend)
    return sub


def install_backend(
    backend,                            # repro.backends.Backend
    *,
    ops: Sequence[str] | None = None,
    dtype=None,
    sizes: Sequence[int] | None = None,
    runtime=None,                       # AdsalaRuntime to register into
    registry=None,                      # ModelRegistry to persist into
    log: Callable[[str], None] | None = None,
    **install_kw,
) -> dict[str, TunedSubroutine]:
    """Sweep all (or selected) ops of one execution backend in one call.

    The backend supplies its own knob space and calibration timer, so the
    identical install pipeline runs against any registered implementation —
    the repo analogue of installing ADSALA on MKL and then on BLIS.  Tuned
    artifacts are optionally registered into a live runtime and persisted
    backend-tagged through a :class:`~repro.core.registry.ModelRegistry`.
    """
    dtype = np.float32 if dtype is None else dtype
    dtype_bytes = int(np.dtype(dtype).itemsize)
    out: dict[str, TunedSubroutine] = {}
    for op in (tuple(ops) if ops else backend.ops()):
        space = (backend.knob_space(op, sizes=tuple(sizes)) if sizes
                 else backend.knob_space(op))
        timer = backend.timer_fn(op, dtype)
        sub = install_subroutine(op, space, timer, dtype_bytes=dtype_bytes,
                                 backend=backend.name, **install_kw)
        if registry is not None:
            registry.save(sub)
        if runtime is not None:
            runtime.register(sub)
        out[op] = sub
        if log is not None:
            log(f"[install_backend] {backend.name}/{op}: "
                f"best={sub.model_name} over {len(space)} knobs")
    return out
