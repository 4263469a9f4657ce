"""Loop-aware cost correction for the roofline.

XLA's ``compiled.cost_analysis()`` counts each ``while``-loop body ONCE, so
a 32-layer scanned stack reports ~1/32 of the real FLOPs, and intra-block
chunk loops (flash kv blocks, SSD/WKV chunks) are likewise under-counted.

Correction scheme (every costing compile stays tiny):

  1. Per *unit* (one block of each kind; the whisper encoder block; the
     fused-CE chunk) compile the body under the cell's exact sharding:
       once     — all loops counted once,
       partial  — the unit's chunk-loop family partially inlined
                  (``lax.scan(unroll=2)`` → two trips counted).
  2. Chunk loops have uniform per-trip cost (each flash kv step / SSD chunk
     does identical work), so the per-trip marginal is exactly
     ``partial − once``, and

       unit_total = once + (trips − n_instances) · (partial − once) / n_inst

     with ``trips`` known analytically (nq·nk for flash, ⌈S/L⌉ for SSD/WKV).
  3. Cell total = production cost + Σ_units (count·unit_total −
     prod_copies·unit_once): the full program already contains each unit
     body ``prod_copies`` times (loops-once form).

The zamba2 super-block is decomposed into (mamba2 × 6·supers + tail) and
(shared-attn × supers) so each unit has a single loop family.  Whisper's
dec_cross has two flash instances (self S×S, cross S×enc); their chunk
steps have equal shapes, handled by the n_instances divisor.  The flash
q-loop overhead (an O(Cq·D) divide per q block) is folded into the
marginal — noted approximation, ≪1% of the kv-step einsums.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig, Shape
from repro.models.layers import Ctx
from repro.models.sharding import ShardingRules, logical_spec
from .hlo_parse import parse_collectives

__all__ = ["cell_units", "unit_costs", "corrected_costs", "Unit",
           "prune_dominated_candidates"]

_COST_KEYS = ("flops", "bytes", "coll")


@dataclasses.dataclass
class Unit:
    kind: str                 # block kind | 'zamba_shared' | 'ce'
    count: int                # executions per step across the model
    prod_copies: int          # loop-body copies already in the full program
    loop_family: str          # 'attn' | 'ssm' | 'none'
    trips: int                # total chunk-loop trips per execution
    n_instances: int = 1      # loop instances sharing the marginal


def _ceil(a, b):
    return -(-a // b)


def _attn_trips(cfg: ModelConfig, S: int, T: int) -> int:
    nq = _ceil(S, min(cfg.attn_q_chunk, S))
    nk = _ceil(T, min(cfg.attn_k_chunk, T))
    return nq * nk


def cell_units(cfg: ModelConfig, shape: Shape) -> list[Unit]:
    S = 1 if shape.kind == "decode" else shape.seq_len
    decode = shape.kind == "decode"
    units: list[Unit] = []
    mamba_count, mamba_copies = 0, 0
    for kind, repeat in cfg.segments():
        if kind == "zamba_super":
            mamba_count += repeat * cfg.shared_attn_every
            mamba_copies += 1
            units.append(Unit("zamba_shared", repeat, 1,
                              "none" if decode else "attn",
                              0 if decode else _attn_trips(cfg, S, S)))
        elif kind == "mamba2":
            mamba_count += repeat
            mamba_copies += 1
        else:
            if kind in ("attn", "moe", "enc"):
                fam = "none" if decode else "attn"
                trips = 0 if decode else _attn_trips(cfg, S, S)
                units.append(Unit(kind, repeat, 1, fam, trips))
            elif kind == "rwkv6":
                fam = "none" if decode else "ssm"
                trips = 0 if decode else _ceil(S, cfg.rwkv_chunk)
                units.append(Unit(kind, repeat, 1, fam, trips))
            elif kind == "dec_cross":
                fam = "none" if decode else "attn"
                trips = (0 if decode else
                         _attn_trips(cfg, S, S) +
                         _attn_trips(cfg, S, cfg.enc_seq))
                units.append(Unit(kind, repeat, 1, fam, trips,
                                  n_instances=1 if decode else 2))
            else:
                raise ValueError(kind)
    if mamba_count:
        fam = "none" if decode else "ssm"
        trips = 0 if decode else _ceil(S, cfg.ssm_chunk)
        units.append(Unit("mamba2", mamba_count, mamba_copies, fam, trips))
    if cfg.family == "audio" and not decode:
        units.append(Unit("enc", cfg.n_enc_layers, 1, "attn",
                          _attn_trips(cfg, cfg.enc_seq, cfg.enc_seq)))
    if shape.kind == "train" and cfg.ce_chunk:
        units.append(Unit("ce", _ceil(shape.seq_len, cfg.ce_chunk), 1,
                          "none", 0))
    return units


# ---------------------------------------------------------------------------
# abstract-input builders
# ---------------------------------------------------------------------------

def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _act(cfg, B, S, mesh, rules):
    spec = logical_spec(rules, mesh, ("batch", "seq", "embed"),
                        dims=(B, S, cfg.d_model))
    return _sds((B, S, cfg.d_model), np.dtype(cfg.compute_dtype), mesh, spec)


def _with_specs(tree_abstract, rules, mesh, spec_builder):
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree_abstract)
    specs = spec_builder(shapes)
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs)


def _strip_leading(tree_abstract, rules, mesh, spec_builder):
    stripped = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree_abstract)
    return _with_specs(stripped, rules, mesh, spec_builder)


def _compile_cost(fn, args, mesh) -> dict:
    t0 = time.perf_counter()
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        coll = parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total_operand_bytes"]),
            "compile_s": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# unit cost
# ---------------------------------------------------------------------------

def _one_layer(cache):
    """One layer's cache as the one-layer stack a block's cache view reads
    (None stays None)."""
    return None if cache is None else jax.tree.map(lambda t: t[None], cache)


def _layer_of(stack):
    return jax.tree.map(lambda t: t[0], stack)


def unit_costs(cfg: ModelConfig, unit: Unit, shape: Shape, mesh,
               rules: ShardingRules, params_abstract,
               caches_abstract) -> dict:
    """Returns {"once": cost, "total": per-execution corrected cost}."""
    from repro.launch.specs import param_specs, cache_specs
    from repro.models.transformer import (_apply_block, _apply_layer,
                                          _shared_attn_block)
    from repro.models.layers import LayerCache, chunked_cross_entropy

    B = shape.global_batch
    S = 1 if shape.kind == "decode" else shape.seq_len
    train = shape.kind == "train"
    seg_kinds = [k for k, _ in cfg.segments()]

    def build(unroll2: bool):
        over = {}
        if unroll2 and unit.loop_family == "attn":
            over["unroll_attn"] = 2
        if unroll2 and unit.loop_family == "ssm":
            over["unroll_ssm"] = 2
        cfg_u = dataclasses.replace(cfg, **over) if over else cfg
        ctx = Ctx(cfg_u, mesh, rules)

        if unit.kind == "ce":
            Sx = min(cfg.ce_chunk, shape.seq_len)
            x = _act(cfg, B, Sx, mesh, rules)
            wshape = (cfg.d_model, cfg.vocab)
            wspec = logical_spec(rules, mesh, ("embed_fsdp", "vocab"),
                                 dims=wshape)
            w = _sds(wshape, np.dtype(cfg.compute_dtype), mesh, wspec)
            lbl = _sds((B, Sx), np.int32, mesh,
                       logical_spec(rules, mesh, ("batch", None),
                                    dims=(B, Sx)))

            def ce_fn(xv, wv, lv):
                f = lambda xx, ww: chunked_cross_entropy(xx, ww, lv, chunk=Sx)
                if train:
                    return jax.grad(f, argnums=(0, 1))(xv, wv)
                return f(xv, wv)

            return ce_fn, (x, w, lbl)

        if unit.kind == "zamba_shared":
            shared = _with_specs(params_abstract["shared_attn"], rules, mesh,
                                 lambda t: param_specs(t, rules, mesh))
            seg_i = seg_kinds.index("zamba_super")
            in_proj = _strip_leading(
                params_abstract["segments"][seg_i]["in_proj"], rules, mesh,
                lambda t: param_specs(t, rules, mesh))
            x = _act(cfg, B, S, mesh, rules)
            cc = None
            if shape.kind in ("prefill", "decode"):
                cc = _strip_leading(
                    caches_abstract[seg_i]["attn"], rules, mesh,
                    lambda t: cache_specs(cfg, t, rules, mesh))

            def sh_fn(sh_v, ip_v, x_v, *rest):
                cc_v = rest[0] if cc is not None else None

                def f(sh_i, ip_i, x_i):
                    h, _ = _shared_attn_block(sh_i, ip_i, x_i, x_i, ctx, cc_v)
                    return jnp.sum(h.astype(jnp.float32))

                if train:
                    return jax.grad(jax.checkpoint(f), argnums=(0, 1, 2))(
                        sh_v, ip_v, x_v)
                h, view = _shared_attn_block(sh_v, ip_v, x_v, x_v, ctx,
                                             LayerCache(_one_layer(cc_v), 0))
                return h, _layer_of(view.stack)

            args = [shared, in_proj, x] + ([cc] if cc is not None else [])
            return sh_fn, tuple(args)

        # ordinary block units ------------------------------------------------
        if unit.kind == "enc" and cfg.family == "audio":
            seg_p = params_abstract["encoder"]["blocks"]
            Sx = cfg.enc_seq
            seg_i = None
        elif unit.kind == "mamba2" and "zamba_super" in seg_kinds:
            seg_i = seg_kinds.index("zamba_super")
            seg_p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape[1:], s.dtype),
                params_abstract["segments"][seg_i]["mamba"])
            Sx = S
        else:
            seg_i = seg_kinds.index(unit.kind)
            seg_p = params_abstract["segments"][seg_i]
            Sx = S
        pp = _strip_leading(seg_p, rules, mesh,
                            lambda t: param_specs(t, rules, mesh))
        x = _act(cfg, B, Sx, mesh, rules)
        extras = []
        if unit.kind == "dec_cross":
            extras.append(_act(cfg, B, cfg.enc_seq, mesh, rules))
        cc = None
        if shape.kind in ("prefill", "decode") and unit.kind != "enc":
            if unit.kind == "mamba2" and "zamba_super" in seg_kinds:
                cache_sub = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                    caches_abstract[seg_i]["mamba"])
            else:
                cache_sub = caches_abstract[seg_i]
            cc = _strip_leading(cache_sub, rules, mesh,
                                lambda t: cache_specs(cfg, t, rules, mesh))

        def block_fn(pp_v, x_v, *rest):
            it = list(rest)
            cc_v = it.pop(0) if cc is not None else None
            enc_v = it.pop(0) if unit.kind == "dec_cross" else None

            def f(pp_i, x_i):
                h, _, aux, _ = _apply_block(unit.kind, pp_i, x_i, ctx, cc_v,
                                            enc_out=enc_v)
                return jnp.sum(h.astype(jnp.float32)) + 0.0 * aux

            if train:
                return jax.grad(jax.checkpoint(f), argnums=(0, 1))(pp_v, x_v)
            h, nc2, _, _ = _apply_layer(unit.kind, pp_v, x_v, ctx,
                                        _one_layer(cc_v), 0, enc_out=enc_v)
            return (h, _layer_of(nc2)) if nc2 is not None else h

        args = [pp, x]
        if cc is not None:
            args.append(cc)
        args.extend(extras)
        return block_fn, tuple(args)

    fn_o, args_o = build(unroll2=False)
    once = _compile_cost(fn_o, args_o, mesh)
    total = dict(once)
    if unit.loop_family != "none" and unit.trips > unit.n_instances:
        fn_p, args_p = build(unroll2=True)
        partial = _compile_cost(fn_p, args_p, mesh)
        for k in _COST_KEYS:
            marginal = (partial[k] - once[k]) / unit.n_instances
            total[k] = once[k] + (unit.trips - unit.n_instances) * \
                max(marginal, 0.0)
    return {"once": once, "total": total}


def prune_dominated_candidates(op: str, space, dims_list,
                               *, dtype_bytes: int = 4,
                               slack: float = 0.15):
    """Drop knob candidates the analytic roofline proves dominated at every
    harvested call site.

    For each dims in ``dims_list`` (e.g. the output of
    ``roofline.harvest.harvest_decision_keys``), score every candidate with
    the deterministic v5e cost oracle and keep the union of all candidates
    within ``(1 + slack)`` of that dims' best.  A candidate outside the band
    at *every* site cannot win under any model whose error is below the
    slack, so install-time calibration need not sample it — the dominant
    cost of ahead-of-time tuning.  Returns a new
    :class:`~repro.core.knobs.KnobSpace` preserving the parallelism
    definition (never empty: each site contributes at least its argmin).
    """
    from repro.core.knobs import KnobSpace
    from repro.core.oracle import oracle_time

    dims_list = [tuple(d) for d in dims_list]
    if not dims_list:
        return space
    keep: set[int] = set()
    for dims in dims_list:
        times = np.array([oracle_time(op, dims, c, dtype_bytes=dtype_bytes)
                          for c in space.candidates])
        band = times.min() * (1.0 + slack)
        keep.update(int(i) for i in np.flatnonzero(times <= band))
    cands = [space.candidates[i].dict for i in sorted(keep)]
    return KnobSpace(space.name, cands,
                     parallelism_fn=space._parallelism_fn)


def corrected_costs(prod: dict, unit_records: list[dict]) -> dict:
    """prod: {"flops","bytes","coll"}; records carry Unit + costs."""
    out = {k: prod[k] for k in _COST_KEYS}
    for rec in unit_records:
        u: Unit = rec["unit"]
        for k in _COST_KEYS:
            out[k] += u.count * rec["total"][k] - \
                u.prod_copies * rec["once"][k]
    return out
