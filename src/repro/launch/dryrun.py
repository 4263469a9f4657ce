import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape × mesh) cell this lowers and COMPILES
the real step function (train_step / prefill / serve_step) against abstract
ShapeDtypeStruct inputs on the production mesh — 16×16 single-pod and
2×16×16 multi-pod — then records ``memory_analysis()`` (fits?),
``cost_analysis()`` (FLOPs/bytes for §Roofline) and the collective schedule
parsed from the compiled HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh single --out runs/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The two os.environ lines above MUST run before any other import — jax locks
the device count on first init.  Do not set the flag globally: smoke tests
and benches see 1 device.
"""

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHITECTURES, ALIASES, SHAPES, get_config, \
    shape_applicable
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.specs import (abstract_caches, abstract_train_state,
                                input_specs, rules_for)
from repro.models import decode_step, loss_fn, prefill
from repro.optim import AdamWConfig, adamw_update
from repro.roofline.analysis import model_flops, roofline
from repro.roofline.hlo_parse import parse_collectives


def _active_params(cfg) -> int:
    """Approximate parameter count (active params for MoE)."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    hd = cfg.hd()
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "ssm":
        per = 4 * d * d + 2 * d * cfg.d_ff + d * d
    elif cfg.family == "hybrid":
        d_in = cfg.ssm_expand * d
        per = 2 * d * d_in + d_in * d          # mamba proj in/out
    else:
        attn = d * cfg.n_heads * hd + 2 * d * cfg.kv_heads * hd + \
            cfg.n_heads * hd * d
        if cfg.use_mla:
            attn = (d * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                    + d * (cfg.kv_lora + cfg.qk_rope_dim)
                    + cfg.kv_lora * cfg.n_heads *
                    (cfg.qk_nope_dim + cfg.v_head_dim)
                    + cfg.n_heads * cfg.v_head_dim * d)
        if cfg.n_experts:
            ff = 3 * d * (cfg.moe_d_ff or cfg.d_ff) * \
                (cfg.top_k + cfg.n_shared_experts)
        else:
            ff = (3 if cfg.mlp_type == "swiglu" else 2) * d * cfg.d_ff
        per = attn + ff
    total = emb + L * per
    if cfg.family == "audio":
        total += cfg.n_enc_layers * (4 * d * d + 2 * d * cfg.d_ff)
    return int(total)


def build_step(cfg, shape, mesh, rules, *, adamw=AdamWConfig()):
    """Returns (jitted fn, example abstract args tuple)."""
    binputs = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        aparams, astate, pspecs = abstract_train_state(cfg, rules, mesh)

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, mesh=mesh, rules=rules),
                has_aux=True)(params)
            new_p, new_s, om = adamw_update(params, grads, opt_state, adamw)
            return new_p, new_s, {"loss": loss, **metrics, **om}

        fn = jax.jit(train_step, donate_argnums=(0, 1))
        return fn, (aparams, astate, binputs)

    serve_rules = rules
    aparams, _, pspecs = abstract_train_state(cfg, serve_rules, mesh)
    # serving deploys low-precision weights (bf16 checkpoint) — no optimizer
    aparams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape,
            np.dtype(cfg.compute_dtype) if s.dtype == np.float32 else s.dtype,
            sharding=s.sharding),
        aparams)
    if shape.kind == "prefill":
        acaches, _ = abstract_caches(cfg, shape.global_batch, shape.seq_len,
                                     serve_rules, mesh)

        def prefill_step(params, batch, caches):
            return prefill(params, batch, caches, cfg, mesh=mesh,
                           rules=serve_rules)

        fn = jax.jit(prefill_step, donate_argnums=(2,))
        return fn, (aparams, binputs, acaches)

    # decode: one token against a seq_len cache
    acaches, _ = abstract_caches(cfg, shape.global_batch, shape.seq_len,
                                 serve_rules, mesh)

    def serve_step(params, batch, caches):
        return decode_step(params, batch["tokens"], caches, cfg, mesh=mesh,
                           rules=serve_rules,
                           enc_out=batch.get("enc_out"))

    fn = jax.jit(serve_step, donate_argnums=(2,))
    return fn, (aparams, binputs, acaches)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             *, rules_override=None, tag: str = "",
             cfg_override: dict | None = None) -> dict:
    cfg = get_config(arch)
    if cfg_override:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = rules_for(mesh, shape.kind, cfg)
    if rules_override:
        rules = rules.replace(**rules_override)
    t0 = time.perf_counter()
    fn, args = build_step(cfg, shape, mesh, rules)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    chips = int(np.prod(list(mesh.shape.values())))
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    mf = model_flops(cfg, shape, _active_params(cfg))

    # loop-aware correction: per-unit body compiles (roofline/costing.py)
    from repro.roofline.costing import cell_units, corrected_costs, unit_costs
    from repro.launch.specs import abstract_caches, abstract_params
    aparams, _ = abstract_params(cfg, rules, mesh)
    acaches = None
    if shape.kind in ("prefill", "decode"):
        acaches, _ = abstract_caches(cfg, shape.global_batch, shape.seq_len,
                                     rules, mesh)
    unit_records = []
    for unit in cell_units(cfg, shape):
        costs = unit_costs(cfg, unit, shape, mesh, rules, aparams, acaches)
        unit_records.append({"unit": unit, **costs})
    corr = corrected_costs({"flops": flops, "bytes": byts,
                            "coll": coll["total_operand_bytes"]},
                           unit_records)

    rep = roofline(arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
                   hlo_flops=corr["flops"], hlo_bytes=corr["bytes"],
                   collective_bytes=corr["coll"], model_flops_=mf)
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0) +
                          (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        cost={"flops_per_device_raw": flops, "bytes_per_device_raw": byts,
              "flops_per_device": corr["flops"],
              "bytes_per_device": corr["bytes"],
              "collective_bytes": corr["coll"]},
        units=[{"kind": u["unit"].kind, "count": u["unit"].count,
                "trips": u["unit"].trips,
                "total_flops": u["total"]["flops"],
                "once_flops": u["once"]["flops"]} for u in unit_records],
        collectives={k: v for k, v in coll.items()
                     if not isinstance(v, dict) or v["count"]},
        roofline=rep.as_dict(),
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{ALIASES.get(arch, arch).replace('-', '_')}_{shape_name}_{mesh_kind}"
    if tag:
        name += f"_{tag}"
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="")
    p.add_argument("--shape", default="")
    p.add_argument("--mesh", default="single", choices=["single", "multi",
                                                        "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="runs/dryrun")
    p.add_argument("--tag", default="")
    p.add_argument("--rules", default="",
                   help="logical=axis1+axis2,... rule overrides")
    p.add_argument("--cfg", default="",
                   help="field=value,... ModelConfig overrides (int/bool)")
    args = p.parse_args(argv)

    overrides = None
    if args.rules:
        overrides = {}
        for kv in args.rules.split(","):
            k, v = kv.split("=")
            overrides[k] = tuple(a for a in v.split("+") if a)

    out = Path(args.out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = list(ARCHITECTURES) if args.all or not args.arch \
        else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                label = f"{arch} × {shape} × {mk}"
                cfg_over = None
                if args.cfg:
                    cfg_over = {}
                    for kv in args.cfg.split(","):
                        k, v = kv.split("=")
                        cfg_over[k] = (v == "true" if v in ("true", "false")
                                       else int(v) if v.isdigit() else v)
                try:
                    rec = run_cell(arch, shape, mk, out,
                                   rules_override=overrides, tag=args.tag,
                                   cfg_override=cfg_over)
                    if rec["status"] == "ok":
                        r = rec["roofline"]
                        print(f"[dryrun] OK  {label}: compile={rec['compile_s']}s "
                              f"peak={rec['memory']['peak_bytes']/1e9:.2f}GB/dev "
                              f"bottleneck={r['bottleneck']}", flush=True)
                    else:
                        print(f"[dryrun] SKIP {label}: {rec['reason']}",
                              flush=True)
                except Exception as e:   # noqa: BLE001
                    failures += 1
                    print(f"[dryrun] FAIL {label}: {type(e).__name__}: {e}",
                          flush=True)
                    traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
