"""The ``Backend`` protocol — one pluggable "black-box BLAS" implementation.

The paper demonstrates the same ML runtime-selection mechanism on two baseline
BLAS libraries (MKL and BLIS); this repo generalises that to any executable
L3 implementation.  A backend bundles everything the ADSALA pipeline needs to
treat an implementation as a tunable black box:

  * ``ops()``          — the subroutines it can execute,
  * ``knob_space(op)`` — its discrete per-op runtime-config candidates
                         (the ``nt`` analogue; here Pallas/cache block shapes),
  * ``default_knob(op)`` — the paper's baseline config (max parallelism),
  * ``timer_fn(op, dtype)`` — a wall-clock timer for install-time calibration,
  * ``execute(op, operands, knob)`` — run the op under a chosen config.

Install-time tuning (:func:`repro.core.tuner.install_backend`), persistence
(:class:`repro.core.registry.ModelRegistry`), runtime decisions
(:class:`repro.core.runtime.AdsalaRuntime`) and dispatch
(:func:`repro.kernels.ops.run_op`) are all keyed by ``backend.name``, so one
process can hold tuned model sets for several implementations side by side —
the repo analogue of the paper's MKL-vs-BLIS comparison on a single harness.
"""

from __future__ import annotations

import abc
from typing import Callable

import numpy as np

from repro.core.features import SUBROUTINE_NDIMS
from repro.core.knobs import Knob, KnobSpace
from repro.core.timing import time_callable

__all__ = ["Backend", "L3_OPS", "grouped_operands"]

#: the six BLAS L3 subroutines of paper Table I
L3_OPS = ("gemm", "symm", "syrk", "syr2k", "trmm", "trsm")

#: dims used to rank candidate parallelism when picking the baseline knob
_BASELINE_DIMS = (4096, 4096, 4096)


def grouped_operands(dims: tuple[int, ...], dtype=np.float32,
                     seed: int = 0) -> tuple:
    """``(x (m, k), w (g, k, n), group_sizes (g,) int32)`` for the grouped
    gemm at ``dims = (m, k, n, g)``: uneven group sizes from the seed (a
    heavy-tailed share per group, about one group in eight empty, the rest
    drawn multinomially), summing to m — a skewed MoE routing."""
    m, k, n, g = dims
    rng = np.random.default_rng(seed)
    share = rng.pareto(1.5, g) + 0.05
    if g > 1:
        share[rng.permutation(g)[: max(1, g // 8)]] = 0.0
    sizes = rng.multinomial(m, share / share.sum()).astype(np.int32)
    return (rng.standard_normal((m, k)).astype(dtype),
            rng.standard_normal((g, k, n)).astype(dtype), sizes)


class Backend(abc.ABC):
    """One executable BLAS L3 implementation with a tunable knob space."""

    #: registry key; also the tag on persisted artifacts and runtime caches
    name: str = "abstract"

    #: True for backends whose executors resolve their own knob (e.g. at jit
    #: trace time) when ``execute`` receives ``knob=None`` — generic dispatch
    #: then skips its pre-selection and forwards the runtime through
    selects_own_knob: bool = False

    #: True when execute_stacked compiles one executable per batch width —
    #: the serving layer then pads buckets to canonical widths to bound the
    #: compile set.  Loop-based backends gain nothing from padding (the
    #: filler rows would just run as extra full ops), so they leave it False.
    jit_stacked: bool = False

    #: True when ``execute`` takes ``layer=``: ``operands[1]`` is then a
    #: stack of layer weights of which the kernel reads that layer in
    #: place.  Dispatch slices the layer out for every other backend.
    reads_weight_stacks: bool = False

    # -- capability ----------------------------------------------------------
    def ops(self) -> tuple[str, ...]:
        return L3_OPS

    def is_available(self) -> bool:
        """Whether this backend can execute on the current host."""
        return True

    def supports_dtype(self, dtype) -> bool:
        """Whether this backend executes ``dtype`` at full precision (the
        conformance gate skips unsupported combinations instead of holding
        them to a tolerance they cannot meet)."""
        return True

    # -- knob space ----------------------------------------------------------
    @abc.abstractmethod
    def knob_space(self, op: str, *,
                   sizes: tuple[int, ...] | None = None) -> KnobSpace:
        """Candidate execution configs for ``op`` on this backend."""

    def default_knob(self, op: str) -> Knob:
        """Baseline config (paper: max threads) = max parallelism."""
        space = self.knob_space(op)
        dims = _BASELINE_DIMS[: SUBROUTINE_NDIMS[op]]
        return space.candidates[int(np.argmax(
            [space.parallelism(c, dims) for c in space.candidates]))]

    # -- execution -----------------------------------------------------------
    @abc.abstractmethod
    def execute(self, op: str, operands: tuple, knob: Knob | None = None,
                **kw):
        """Run ``op`` on ``operands`` under ``knob`` (backend default if
        ``None``); returns the result array."""

    def execute_stacked(self, op: str, operands: tuple,
                        knob: Knob | None = None, **kw):
        """Run ``op`` over operands carrying a leading batch axis — the
        serving layer's bucket-execution primitive (all requests in a bucket
        share dims/dtype, so one knob covers the whole stack).

        Operands of one-lower rank than the stack are *shared* across it
        (a 2-D weight against batched activations) and pass through whole.

        The base implementation unstacks, loops :meth:`execute`, and
        restacks; backends that can execute a stack natively (vmap, batched
        BLAS, strided GEMM) override this with the one-call version.
        """
        batch = int(operands[0].shape[0])
        rank = operands[0].ndim
        outs = [self.execute(op,
                             tuple(x[i] if getattr(x, "ndim", rank) == rank
                                   else x for x in operands), knob, **kw)
                for i in range(batch)]
        return np.stack([np.asarray(o) for o in outs])

    def make_operands(self, op: str, dims: tuple[int, ...],
                      dtype=np.float32, seed: int = 0) -> tuple:
        """Random operands of the right shapes (calibration inputs).  Seeded
        identically across backends so cross-backend checks compare the same
        problem instance."""
        if op == "grouped_gemm":
            return grouped_operands(dims, dtype, seed)
        from repro.kernels.cpu_blocked import make_operands
        return make_operands(op, dims, dtype, seed)

    def prepare(self, operands: tuple) -> tuple:
        """Convert operands to this backend's native array type (hook so
        timers exclude one-time host↔device transfer)."""
        return operands

    # -- calibration ---------------------------------------------------------
    def timer_fn(self, op: str, dtype=np.float32, *, warmup: int = 1,
                 repeats: int = 2) -> Callable[[tuple, Knob], float]:
        """``timer(dims, knob) -> seconds`` for the install-time sweep, with
        operand caching across the per-dims knob sweep."""
        cache: dict = {"dims": None, "operands": None}

        def timer(dims: tuple, knob: Knob) -> float:
            if cache["dims"] != dims:
                cache["dims"] = dims
                cache["operands"] = self.prepare(self.make_operands(
                    op, dims, dtype, seed=hash(dims) % (2 ** 31)))
            operands = cache["operands"]
            return time_callable(lambda: self.execute(op, operands, knob),
                                 warmup=warmup, repeats=repeats)

        return timer

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r})"
