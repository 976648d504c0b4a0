"""Loop-corrected roofline analysis (EXPERIMENTS.md §Roofline).

XLA's cost analysis counts a while-loop body ONCE regardless of trip count
(verified: scan-of-8-matmuls reports 1/8 the flops of the unrolled version).
Our models scan over `num_periods`, so aggregate program costs undercount by
~nP. Correction: compile each period body STANDALONE with identical shardings
and add (nP - 1) x its costs to the aggregate:

  train   : total = agg + (nP-1) * (fwd_body + grad_body)
            (full-remat bwd scan body = refwd + bwd = grad_body exactly)
  prefill : total = agg + (nP-1) * prefill_body
  decode  : total = agg + (nP-1) * decode_body

Collective bytes get the same correction (bodies parsed separately).
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from repro.launch.dryrun import (
    HBM_BW,
    LINK_BW,
    PEAK_FLOPS,
    collective_bytes_per_device,
    lower_cell,
)
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.models import transformer
from repro.models.common import (
    ModelConfig,
    abstract_period_params,
    count_active_params,
    period_pspecs,
)
from repro.sharding.context import use_mesh
from repro.sharding.partitioning import (
    batch_spec,
    cache_slice_pspecs,
    named,
    named_sanitized,
)


def _costs_of(compiled, chips: int) -> dict:
    cost = compiled.cost_analysis()
    coll = collective_bytes_per_device(compiled.as_text(), default_group=chips)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll["total"],
    }


def body_costs(cfg: ModelConfig, shape, mesh) -> dict:
    """Compile the period body standalone; returns per-device costs."""
    chips = mesh.size
    B, S = shape.global_batch, shape.seq_len
    app = abstract_period_params(cfg)
    pspec = period_pspecs(cfg)
    sds = jax.ShapeDtypeStruct
    x = sds((B, 1 if shape.kind == "decode" else S, cfg.d_model), cfg.dtype)
    xspec = batch_spec(mesh, B, 2)
    enc = (
        sds((B, cfg.num_encoder_tokens, cfg.d_model), cfg.dtype)
        if cfg.num_encoder_tokens
        else None
    )
    espec = batch_spec(mesh, B, 2)

    with mesh, use_mesh(mesh):
        if shape.kind == "train":
            def fwd(xx, lp, ee=None):
                h, aux = transformer.apply_period_train(cfg, xx, lp, ee)
                return h, aux

            def lossy(xx, lp, ee=None):
                h, aux = transformer.apply_period_train(cfg, xx, lp, ee)
                return h.astype(jnp.float32).sum() + aux

            grad_fn = jax.grad(lossy, argnums=(0, 1))
            args = (x, app) + ((enc,) if enc is not None else ())
            ins = (NamedSharding(mesh, xspec), named_sanitized(mesh, pspec, app)) + (
                (NamedSharding(mesh, espec),) if enc is not None else ()
            )
            cf = jax.jit(fwd, in_shardings=ins).lower(*args).compile()
            cg = jax.jit(grad_fn, in_shardings=ins).lower(*args).compile()
            f, g = _costs_of(cf, chips), _costs_of(cg, chips)
            return {k: f[k] + g[k] for k in f}

        if shape.kind == "prefill":
            def pf(xx, lp, ee=None):
                return transformer.apply_period_prefill(cfg, xx, lp, ee, max_len=S)

            args = (x, app) + ((enc,) if enc is not None else ())
            ins = (NamedSharding(mesh, xspec), named_sanitized(mesh, pspec, app)) + (
                (NamedSharding(mesh, espec),) if enc is not None else ()
            )
            cp = jax.jit(pf, in_shardings=ins).lower(*args).compile()
            return _costs_of(cp, chips)

        # decode
        cache_slice = transformer.abstract_cache_slice(cfg, B, S)
        cspec = cache_slice_pspecs(cfg, mesh, B, mode="decode")
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)

        def dec(xx, lp, cs, pp):
            return transformer.apply_period_decode(cfg, xx, lp, cs, pp)

        ins = (
            NamedSharding(mesh, xspec),
            named_sanitized(mesh, pspec, app),
            named_sanitized(mesh, cspec, cache_slice),
            NamedSharding(mesh, batch_spec(mesh, B, 0)),
        )
        cd = (
            jax.jit(dec, in_shardings=ins, donate_argnums=(2,))
            .lower(x, app, cache_slice, pos)
            .compile()
        )
        return _costs_of(cd, chips)


_DRYRUN_CACHE: dict = {}


def _load_dryrun(path: str) -> dict:
    if path not in _DRYRUN_CACHE:
        recs = {}
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    r = json.loads(line)
                    recs[(r["arch"], r["shape"], r["mesh"])] = r
        _DRYRUN_CACHE[path] = recs
    return _DRYRUN_CACHE[path]


def corrected_record(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    dryrun_results: str = "experiments/dryrun/results.jsonl",
) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size

    mesh_name = "2x16x16" if multi_pod else "16x16"
    record = _load_dryrun(dryrun_results).get((arch, shape_name, mesh_name))
    if record is None:  # fall back to a fresh full-program compile
        record, _mem, _cost = lower_cell(arch, shape_name, multi_pod)
    body = body_costs(cfg, shape, mesh)
    nP = cfg.num_periods

    flops = record["flops_per_device"] + (nP - 1) * body["flops"]
    bytes_ = record["bytes_per_device"] + (nP - 1) * body["bytes"]
    coll = record["collective_bytes_per_device"]["total"] + (nP - 1) * body["coll"]

    tokens = (
        shape.global_batch
        if shape.kind == "decode"
        else shape.global_batch * shape.seq_len
    )
    n_active = count_active_params(cfg)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens

    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_ / HBM_BW,
        "collective_s": coll / LINK_BW,
    }
    bottleneck = max(terms, key=terms.get).replace("_s", "")
    step_s = max(terms.values())
    achieved = model_flops / chips / step_s if step_s > 0 else 0.0

    return dict(
        record,
        corrected=True,
        body_flops=body["flops"],
        body_bytes=body["bytes"],
        body_coll=body["coll"],
        flops_per_device=flops,
        bytes_per_device=bytes_,
        collective_total_bytes=coll,
        **terms,
        bottleneck=bottleneck,
        model_flops_global=model_flops,
        useful_flops_ratio=model_flops / (flops * chips) if flops else 0.0,
        roofline_fraction=achieved / PEAK_FLOPS,
        step_time_s=step_s,
    )


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument(
        "--dryrun-results", default="experiments/dryrun/results.jsonl",
        help="reuse full-program aggregates from a dry-run results file",
    )
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "roofline.jsonl")
    done = set()
    if args.skip_existing and os.path.exists(path):
        with open(path) as f:
            done = {
                (r["arch"], r["shape"], r["mesh"])
                for r in map(json.loads, f)
            }

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                if (arch, shape_name, mesh_name) in done:
                    continue
                try:
                    rec = corrected_record(
                        arch, shape_name, mp, dryrun_results=args.dryrun_results
                    )
                    print(
                        f"{arch:26s} {shape_name:12s} {mesh_name:8s} "
                        f"C={rec['compute_s']:.4f}s M={rec['memory_s']:.4f}s "
                        f"X={rec['collective_s']:.4f}s -> {rec['bottleneck']:10s} "
                        f"useful={rec['useful_flops_ratio']:.2f} "
                        f"roofline={rec['roofline_fraction']:.3f}",
                        flush=True,
                    )
                    with open(path, "a") as f:
                        f.write(json.dumps(rec) + "\n")
                except Exception:
                    import traceback

                    traceback.print_exc()


if __name__ == "__main__":
    main()
