"""Perf hillclimbing driver (EXPERIMENTS.md §Perf).

Measures named config VARIANTS of the three chosen cells and logs
hypothesis -> change -> before/after on the dominant roofline term.

  PYTHONPATH=src python -m repro.launch.hillclimb --cell musicgen_prefill
"""
import argparse
import dataclasses
import json
import os

from repro.configs import SHAPES, get_config
from repro.launch import roofline as rl
from repro.launch.mesh import force_host_devices

# Registry of (arch, shape, [(variant_name, config_transform), ...])
def _v(name, **kw):
    return (name, lambda cfg: dataclasses.replace(cfg, **kw))


CELLS = {
    "musicgen_prefill": {
        "arch": "musicgen-large",
        "shape": "prefill_32k",
        "variants": [
            ("baseline", lambda cfg: cfg),
            _v("bf16_scores", attn_bf16_scores=True),
            _v("seq_parallel", seq_parallel=True),
            _v("seq_parallel+bf16", seq_parallel=True, attn_bf16_scores=True),
        ],
    },
    "jamba_decode": {
        "arch": "jamba-1.5-large-398b",
        "shape": "decode_32k",
        "variants": [
            ("baseline", lambda cfg: cfg),
            _v("ep_experts", moe_ep=True),
            _v("ep+tp_resident", moe_ep=True, fsdp_params=False),
        ],
    },
    "llama_decode": {
        "arch": "llama-3.2-vision-90b",
        "shape": "decode_32k",
        "variants": [
            ("baseline", lambda cfg: cfg),
            _v("tp_resident", fsdp_params=False),
            _v("tp_resident+int8kv", fsdp_params=False, kv_quant=True),
            _v("int8kv_only", kv_quant=True),
        ],
    },
}


def measure(arch, shape_name, cfg, multi_pod=False):
    """corrected_record but with an explicit (possibly variant) config."""
    import repro.configs.registry as registry

    # Temporarily override the registry so lower_cell/body_costs see the variant
    orig = registry.get_config
    registry.get_config = lambda a: cfg if a == arch else orig(a)
    import repro.launch.dryrun as dr

    orig_dr = dr  # lower_cell uses repro.configs get_config import
    import repro.configs as configs_pkg

    orig_pkg = configs_pkg.get_config
    configs_pkg.get_config = registry.get_config
    rl.get_config = registry.get_config
    dr.get_config = registry.get_config
    try:
        rec = rl.corrected_record(arch, shape_name, multi_pod,
                                  dryrun_results="/nonexistent")
    finally:
        registry.get_config = orig
        configs_pkg.get_config = orig_pkg
        rl.get_config = orig
        dr.get_config = orig
    return rec


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--variant", default=None)
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    spec = CELLS[args.cell]
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.cell}.jsonl")
    for name, tf in spec["variants"]:
        if args.variant and name != args.variant:
            continue
        cfg = tf(get_config(spec["arch"]))
        rec = measure(spec["arch"], spec["shape"], cfg)
        rec["variant"] = name
        rec["cell"] = args.cell
        print(
            f"{args.cell:18s} {name:22s} C={rec['compute_s']:.4f} "
            f"M={rec['memory_s']:.4f} X={rec['collective_s']:.4f} "
            f"-> {rec['bottleneck']} step={rec['step_time_s']:.4f}s",
            flush=True,
        )
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
