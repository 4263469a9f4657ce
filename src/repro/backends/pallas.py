"""The ``pallas`` backend: the repo's Pallas TPU kernels (kernels.ops).

On a TPU the kernels run compiled; where JAX's backend is the CPU they run
in interpret mode (still jit-compiled, so post-warmup wall-clock is
meaningful for calibration at small scales).  The mode is decided on the
first execution, not at construction: building the backend (which happens
when ``repro.backends`` is imported) must not initialise a JAX backend and
claim the chip.  Any other platform is refused, and a failed backend
initialisation propagates — it never silently becomes interpret mode.  The
mode can be forced via the constructor.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.knobs import Knob, KnobSpace

from .base import Backend

__all__ = ["PallasBackend"]


def _interpret_mode() -> bool:
    """Compiled on a TPU, interpreted on the CPU; raises on anything else.

    JAX starts the TPU quietly: when ``JAX_PLATFORMS`` does not name the
    platforms, a failed TPU start (the chip held by another process, say)
    is only recorded, and the default backend becomes the CPU.  That CPU is
    refused here, so a host whose chip failed never runs the interpreter.
    """
    import jax
    from jax._src import xla_bridge
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(f"the pallas backend runs on a TPU (compiled) or "
                           f"the CPU (interpret mode), not on {platform!r}")
    if platform == "cpu" and "tpu" in xla_bridge._backend_errors:
        raise RuntimeError(
            f"the TPU failed to start ({xla_bridge._backend_errors['tpu']}); "
            f"set JAX_PLATFORMS=cpu to run the pallas kernels in interpret "
            f"mode on purpose")
    return platform == "cpu"


class PallasBackend(Backend):
    name = "pallas"
    selects_own_knob = True     # ops.py selects at jit trace time
    jit_stacked = True          # vmap compiles per (shape, width)
    reads_weight_stacks = True  # gemm / grouped_gemm take ``layer=``

    def __init__(self, *, interpret: bool | None = None) -> None:
        self._interpret = interpret

    @property
    def interpret(self) -> bool:
        """Whether the kernels run in interpret mode (decided on first use)."""
        if self._interpret is None:
            self._interpret = _interpret_mode()
        return self._interpret

    def knob_space(self, op: str, *,
                   sizes: tuple[int, ...] | None = None) -> KnobSpace:
        from repro.kernels.ops import knob_space_for
        return knob_space_for(op, sizes=tuple(sizes) if sizes else None)

    def supports_dtype(self, dtype) -> bool:
        from .ref import _jax_supports
        return _jax_supports(dtype)

    def default_knob(self, op: str) -> Knob:
        from repro.kernels.ops import default_knob
        return default_knob(op)

    def prepare(self, operands: tuple) -> tuple:
        return tuple(jnp.asarray(x) for x in operands)

    def execute(self, op: str, operands: tuple, knob: Knob | None = None,
                **kw):
        from repro.kernels.ops import PALLAS_OPS
        if "interpret" not in kw:
            kw["interpret"] = self.interpret
        return PALLAS_OPS[op](*operands, knob=knob, **kw)

    def execute_stacked(self, op: str, operands: tuple,
                        knob: Knob | None = None, **kw):
        from repro.kernels.ops import PALLAS_OPS
        if "interpret" not in kw:
            kw["interpret"] = self.interpret
        # the kernels take the leading batch axis natively — it becomes the
        # leading (parallel) grid dimension of ONE pallas_call, replacing
        # the old jax.vmap lift; the knob decision still runs once at trace
        # time for the whole stack
        return PALLAS_OPS[op](*(jnp.asarray(x) for x in operands),
                              knob=knob, **kw)
