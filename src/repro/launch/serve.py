"""Batched serving driver: continuous-batching prefill + decode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --requests 8 --max-new 32

A minimal production-shaped server core: a request queue, batched prefill
(padded to the batch's max prompt), then step-synchronous batched decode
with greedy/temperature sampling and per-sequence stop handling.  The same
``prefill`` / ``decode_step`` functions are what the dry-run lowers for the
``prefill_32k`` / ``decode_32k`` / ``long_500k`` cells.

The session writes profiler spans (``jax.profiler.TraceAnnotation``, inert
without a profiler session) on the device trace's clock: ``serve.prefill``
around a whole prefill, with ``serve.init_cache`` and ``serve.prefill_step``
inside it; ``serve.decode_step`` around each decode step's dispatch; and
``serve.sample`` around sampling.

Counter: ``ServeSession.routed_rows`` holds the last prefill's token-slots
per expert of each MoE layer, ``(L_moe, E)`` int32, as the prefill program
returned it: left on the device (read it after the work it counts, it costs
the step no host sync), None for a model without MoE layers or before the
first prefill.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (decode_step, init_decode_state, init_params,
                          prefill)
from repro.models.transformer import _run_encoder
from repro.models.layers import Ctx

__all__ = ["ServeSession", "init_serving_params", "main"]


def init_serving_params(cfg, *, seed: int = 0, mesh=None, rules=None):
    """Random parameters for serving, initialised under ``jit`` so no host
    or float32 copy of a stacked per-layer leaf is materialised.  Stored in
    ``cfg.param_dtype``; on a ``mesh`` each leaf is placed by
    :func:`repro.launch.specs.param_specs` under ``rules``."""
    def init():
        return init_params(jax.random.PRNGKey(seed), cfg)

    if mesh is None:
        return jax.jit(init)()
    from repro.launch.specs import param_specs, tree_shardings
    shapes = jax.eval_shape(init)
    shardings = tree_shardings(shapes, param_specs(shapes, rules, mesh), mesh)
    return jax.jit(init, out_shardings=shardings)()


class _Spanned:
    """A jitted step whose every call runs under the profiler span ``name``.
    Any other attribute (``lower``, ``trace``, ...) is the jitted
    function's own."""

    def __init__(self, name: str, step) -> None:
        self._name, self._step = name, step

    def __call__(self, *args, **kw):
        with TraceAnnotation(self._name):
            return self._step(*args, **kw)

    def __getattr__(self, attr):
        return getattr(self._step, attr)


@dataclasses.dataclass
class ServeSession:
    cfg: object
    params: dict
    max_len: int
    mesh: object = None
    rules: object = None

    def __post_init__(self):
        cfg = self.cfg
        self.routed_rows = None
        self._prefill = _Spanned("serve.prefill_step", jax.jit(
            lambda p, b, c: prefill(p, b, c, cfg, mesh=self.mesh,
                                    rules=self.rules, return_rows=True),
            donate_argnums=(2,)))
        self._decode = _Spanned("serve.decode_step", jax.jit(
            lambda p, t, c, e: decode_step(p, t, c, cfg, mesh=self.mesh,
                                           rules=self.rules, enc_out=e),
            donate_argnums=(2,), static_argnums=()))

    def prefill(self, prompts: np.ndarray, *,
                frames: np.ndarray | None = None,
                vision: np.ndarray | None = None):
        """prompts: (B, S_prompt) int32 → (last-token logits (B, 1, V),
        filled caches, encoder output or None)."""
        with TraceAnnotation("serve.prefill"):
            cfg = self.cfg
            B = prompts.shape[0]
            with TraceAnnotation("serve.init_cache"):
                caches = init_decode_state(cfg, B, self.max_len,
                                           dtype=jnp.dtype(cfg.compute_dtype))
            batch = {"tokens": jnp.asarray(prompts)}
            enc_out = None
            if cfg.family == "audio":
                batch["frames"] = jnp.asarray(frames)
                enc_out = _run_encoder(self.params, batch["frames"], Ctx(cfg))
            if cfg.family == "vlm":
                batch["vision"] = jnp.asarray(vision)
            logits, caches, self.routed_rows = self._prefill(
                self.params, batch, caches)
            return logits, caches, enc_out

    def generate(self, prompts: np.ndarray, *, max_new: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 frames: np.ndarray | None = None,
                 vision: np.ndarray | None = None) -> np.ndarray:
        """prompts: (B, S_prompt) int32 → (B, max_new) int32."""
        logits, caches, enc_out = self.prefill(prompts, frames=frames,
                                               vision=vision)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits[:, -1], temperature, key)
        for i in range(max_new):
            out.append(np.asarray(tok))
            logits, caches = self._decode(self.params, tok, caches, enc_out)
            key, sub = jax.random.split(key)
            tok = self._sample(logits[:, -1], temperature, sub)
        return np.concatenate(out, axis=1)

    @staticmethod
    def _sample(logits, temperature, key):
        with TraceAnnotation("serve.sample"):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            return jax.random.categorical(
                key, logits / temperature, axis=-1)[:, None].astype(jnp.int32)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    # parameters held in the compute dtype: a full-width 4B model then
    # takes 8 GB instead of 16, and fits one 16 GB chip
    cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
    params = init_serving_params(cfg)
    sess = ServeSession(cfg=cfg, params=params,
                        max_len=args.prompt_len + args.max_new + 8)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab,
                           size=(args.requests, args.prompt_len),
                           dtype=np.int32)
    kw = {}
    if cfg.family == "audio":
        kw["frames"] = rng.standard_normal(
            (args.requests, cfg.enc_seq, cfg.d_model)).astype(np.float32)
    if cfg.family == "vlm":
        kw["vision"] = rng.standard_normal(
            (args.requests, cfg.vision_tokens, cfg.d_model)).astype(np.float32)
    t0 = time.perf_counter()
    out = sess.generate(prompts, max_new=args.max_new,
                        temperature=args.temperature, **kw)
    dt = time.perf_counter() - t0
    toks = args.requests * args.max_new
    print(f"[serve] generated {out.shape} in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. prefill+compile)")
    print(out[:, :12])
    return out


if __name__ == "__main__":
    main()
