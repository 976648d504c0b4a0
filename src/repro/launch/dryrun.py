import argparse
import os
import json
import re
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.sharding.context import use_mesh
from repro.train.optimizer import OptConfig
from repro.train import train_step as ts

# ---------------------------------------------------------------- constants
PEAK_FLOPS = 197e12  # TPU v5e bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link

_COLL_RE = re.compile(
    r"=\s*\(?(\w+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce-start|all-gather-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-reduce|all-gather|collective-permute)\(",
)
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*\}[^}]*\}|\[[\d,]+\]<=\[[^\]]*\](?:T\([^)]*\))?)"
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return default
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}", 1)[0]
        return len(first.split(","))
    # iota form: [n_groups,group_size]<=[dims...](T(perm))?
    dims = g[1:].split("]", 1)[0].split(",")
    return int(dims[-1])  # group_size is the trailing dim


def collective_bytes_per_device(hlo_text: str, default_group: int) -> dict:
    """Parse per-device link bytes from the compiled HLO, with ring-algorithm
    factors per op kind. Returns {op_kind: bytes, 'total': bytes}."""
    out: dict[str, float] = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        dtype, shape_s, kind = m.group(1), m.group(2), m.group(3)
        kind = kind.replace("-start", "")
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in shape_s.split(","):
            if d:
                nbytes *= int(d)
        g = _group_size(line, default_group)
        if g <= 1:
            continue
        if kind == "all-gather":
            moved = nbytes * (g - 1) / g  # result is the gathered buffer
        elif kind == "all-reduce":
            moved = nbytes * 2 * (g - 1) / g
        elif kind == "reduce-scatter":
            moved = nbytes * (g - 1)  # result is the scattered shard
        elif kind == "all-to-all":
            moved = nbytes * (g - 1) / g
        else:  # collective-permute
            moved = nbytes
        out[kind] = out.get(kind, 0.0) + moved
        total += moved
    out["total"] = total
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Build + lower + compile one (arch, shape, mesh) cell. Returns record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    ocfg = OptConfig(
        moment_dtype=cfg.optim_moment_dtype, master_fp32=cfg.optim_master_fp32
    )

    from repro.models.common import abstract_params, count_active_params

    aparams = abstract_params(cfg)

    with mesh, use_mesh(mesh):
        if shape.kind == "train":
            step = ts.make_train_step(cfg, ocfg)
            ins, outs = ts.train_step_shardings(cfg, ocfg, mesh, shape)
            from repro.train.optimizer import abstract_opt_state

            args = (aparams, abstract_opt_state(ocfg, aparams),
                    ts.abstract_train_batch(cfg, shape))
            jitted = jax.jit(step, in_shardings=ins, out_shardings=outs,
                             donate_argnums=(0, 1))
        elif shape.kind == "prefill":
            step = ts.make_prefill_step(cfg)
            ins, outs = ts.prefill_shardings(cfg, mesh, shape)
            args = (aparams, ts.abstract_prefill_batch(cfg, shape))
            jitted = jax.jit(step, in_shardings=ins, out_shardings=outs)
        else:  # decode
            step = ts.make_serve_step(cfg)
            ins, outs = ts.serve_shardings(cfg, mesh, shape)
            cache, tok, pos = ts.abstract_serve_inputs(cfg, shape)
            args = (aparams, cache, tok, pos)
            jitted = jax.jit(step, in_shardings=ins, out_shardings=outs,
                             donate_argnums=(1,))

        t0 = time.time()
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_per_device(hlo, default_group=chips)

    flops_per_dev = float(cost.get("flops", 0.0))
    bytes_per_dev = float(cost.get("bytes accessed", 0.0))

    # tokens processed by the step (for MODEL_FLOPS = 6*N_active*D)
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    n_active = count_active_params(cfg)
    model_flops = 6 * n_active * tokens if shape.kind == "train" else 2 * n_active * tokens

    compute_s = flops_per_dev / PEAK_FLOPS
    memory_s = bytes_per_dev / HBM_BW
    collective_s = coll["total"] / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_per_dev,
        "bytes_per_device": bytes_per_dev,
        "collective_bytes_per_device": coll,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops_global": model_flops,
        "useful_flops_ratio": (
            model_flops / (flops_per_dev * chips) if flops_per_dev else 0.0
        ),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    return record, mem, cost


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: applicable)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results_path = os.path.join(args.out, "results.jsonl")
    done = set()
    if args.skip_existing and os.path.exists(results_path):
        with open(results_path) as f:
            for line in f:
                r = json.loads(line)
                done.add((r["arch"], r["shape"], r["mesh"]))

    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        for shape_name in shapes:
            for multi_pod in meshes:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                if (arch, shape_name, mesh_name) in done:
                    continue
                tag = f"{arch} x {shape_name} x {mesh_name}"
                print(f"=== {tag} ===", flush=True)
                try:
                    record, mem, cost = lower_cell(arch, shape_name, multi_pod)
                    print(f"memory_analysis: {mem}", flush=True)
                    print(
                        "cost_analysis: flops={:.3e} bytes={:.3e}".format(
                            record["flops_per_device"], record["bytes_per_device"]
                        ),
                        flush=True,
                    )
                    print(
                        "roofline: compute={compute_s:.4f}s memory={memory_s:.4f}s "
                        "collective={collective_s:.4f}s bottleneck={bottleneck} "
                        "useful={useful_flops_ratio:.2f}".format(**record),
                        flush=True,
                    )
                    with open(results_path, "a") as f:
                        f.write(json.dumps(record) + "\n")
                    n_ok += 1
                except Exception:
                    traceback.print_exc()
                    with open(os.path.join(args.out, "failures.log"), "a") as f:
                        f.write(f"{tag}\n{traceback.format_exc()}\n")
                    n_fail += 1
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
