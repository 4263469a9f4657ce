"""Pipeline parallelism: GPipe-style microbatch schedule over a stage axis.

For the assigned model sizes on a 256-chip pod, DP×TP(×EP) is the efficient
regime (stage bubbles would waste >10% at these depths), so the dry-runs use
DP×TP; this module provides the PP schedule as a first-class option for
deeper-than-memory models and is exercised by tests on a small mesh.

Implementation: the layer stack is split into S stages; each microbatch
flows stage-by-stage under ``shard_map`` over the ``stage`` mesh axis with
``jax.lax.ppermute`` moving activations to the next stage.  The classic
GPipe schedule runs S + M - 1 ticks for M microbatches; bubble fraction
(S-1)/(S+M-1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe_forward", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages + n_microbatches - 1)


def gpipe_forward(stage_fn, params_per_stage, x, *, mesh: Mesh,
                  n_microbatches: int, stage_axis: str = "stage"):
    """Run ``stage_fn(stage_params, x)`` through S pipeline stages.

    params_per_stage: pytree with leading stage axis (sharded over
    ``stage_axis``).  x: (B, ...) global batch; B must divide into
    ``n_microbatches``.  Returns the pipeline output (same shape as x).
    """
    S = mesh.shape[stage_axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)
    def run(stage_params, micro_all):
        stage_params = jax.tree.map(lambda t: t[0], stage_params)
        sid = jax.lax.axis_index(stage_axis)
        n_ticks = S + n_microbatches - 1
        buf = jnp.zeros((mb,) + micro_all.shape[2:], micro_all.dtype)
        outs = jnp.zeros_like(micro_all)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            mb_idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = jnp.where(t < n_microbatches,
                               micro_all[mb_idx],
                               jnp.zeros_like(buf))
            cur = jnp.where(sid == 0, inject, buf)
            y = stage_fn(stage_params, cur)
            # last stage emits microbatch t-(S-1)
            out_idx = jnp.clip(t - (S - 1), 0, n_microbatches - 1)
            emit = (sid == S - 1) & (t >= S - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o, outs)
            # rotate activations to the next stage
            nxt = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(n_ticks))
        # every stage holds `outs`; only the last stage's copy is real —
        # broadcast it (psum of masked copies)
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), stage_axis)
        return outs

    out = run(params_per_stage, micro)
    return out.reshape(B, *x.shape[1:])
