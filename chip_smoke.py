"""Smoke run of the system's main paths on one TPU: the quickest proof that
it still starts on the chip.  A smoke run, not a benchmark: the times it
prints include compilation and are not measurements of speed.

Phases, in order; any failure ends the run with a non-zero exit:

A. Stream, batch.  ``Engine(...).run(plan, source)`` on the process backend
   over ``--events`` events of 12 int32 columns whose key column is
   Zipf(1.1) over 100,000 keys.  The chain is a stateless projection, one
   ``device_op`` on the ``affine_pallas`` kernel pinned to jax (batches of
   ``--device-batch`` rows), and a partitioned keyed running aggregate.
   The ordered egress must equal a plain Python reference exactly.
B. Stream, session.  The same plan through ``engine.open(plan)``, pushed in
   four windows; after each window the results equal the reference's
   prefix.  Every jax device worker of A and B must report a TPU.
C. Model server.  ``OrderedServingEngine`` at olmo-1b's published widths
   (bf16, random weights from ``--seed``) answers 8 requests in submission
   order within their token budgets, and one prompt's prefill logits agree
   with a float32 cache-free forward pass.  This phase alone opens the chip
   in this process, after A and B have reaped their device workers.

The last line of standard output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--events N] [--device-batch ROWS] [--seed S]

On a host without a TPU (``JAX_PLATFORMS=cpu``) A and B run, with the
kernel interpreted, and the run then fails for lack of a chip.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

NUM_KEYS = 100_000
ZIPF_S = 1.1
COLUMNS = 12
IO_BATCH = 32
A, B = 3, 7  # the device stage computes x * A + B on every column
REQUESTS = 8
# bf16 weights and activations against a float32 pass over the same
# (bf16-valued) weights: the logits' relative L2 error measured 0.013 at 2
# and 0.014 at 4 layers of olmo-1b width (XLA:CPU); 16 layers should stay
# near 0.02.  Computing below bf16, a broken layer or a wrong cache slot
# lands far above the bound.
LOGITS_REL_L2 = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ stream
def make_events(n: int, seed: int):
    """``n`` events of 12 int32 columns; column 0 is the key."""
    import numpy as np

    rng = np.random.default_rng(seed)
    weights = np.arange(1, NUM_KEYS + 1, dtype=np.float64) ** -ZIPF_S
    cdf = np.cumsum(weights / weights.sum())
    events = rng.integers(0, 1000, size=(n, COLUMNS), dtype=np.int32)
    keys = np.searchsorted(cdf, rng.random(n), side="right")
    events[:, 0] = np.minimum(keys, NUM_KEYS - 1)
    return events


def project(e):
    return [(e[0], e[1] + e[2], e[3] - e[4], e[5], e[6], e[7], e[8], e[9])]


def key_of(t):
    return t[0]


def zero_state():
    return (0, 0)


def running_aggregate(state, key, t):
    n, total = state
    state = (n + 1, total + t[1])
    return state, [(key, n + 1, total + t[1]) + t[2:]]


def reference(events) -> list:
    """What the chain must egress, in order: plain Python over the events."""
    out, state = [], {}
    for e in events.tolist():
        (p,) = project(e)
        d = tuple(x * A + B for x in p)
        n, total = state.get(d[0], (0, 0))
        n, total = n + 1, total + d[1]
        state[d[0]] = (n, total)
        out.append((d[0], n, total) + d[2:])
    return out


def source(events, chunk: int = 65_536):
    for lo in range(0, len(events), chunk):
        yield from map(tuple, events[lo:lo + chunk].tolist())


def stream_plan(device_batch: int):
    from repro.columnar import Schema, device_op
    from repro.core import Engine, EngineConfig, OpSpec, ProcessOptions

    ops = [
        OpSpec("project", "stateless", project, cost_us=2.0),
        device_op("affine", "affine_pallas", Schema.of(*["i4"] * 8),
                  params={"a": A, "b": B}, backend="jax", cost_us=1.0),
        OpSpec("aggregate", "partitioned", running_aggregate, key_fn=key_of,
               num_partitions=16, init_state=zero_state, cost_us=4.0),
    ]
    reorder = 2 * device_batch  # two device batches in flight (PV411)
    engine = Engine(EngineConfig(
        backend="process", num_workers=4, batch_size=IO_BATCH,
        reorder_size=reorder, collect_outputs=True,
        process=ProcessOptions(
            io_batch=IO_BATCH, max_inflight=reorder // IO_BATCH,
            columnar=True, device_batch=device_batch, device_inflight=2,
            device_backend="jax", checkpoint_interval=4 * device_batch,
        ),
    ))
    return engine, engine.plan(ops)


def report_devices(phase: str, devices: list, events: int) -> None:
    if not devices:
        raise RuntimeError(f"phase {phase}: no device worker reported")
    for d in devices:
        log(f"  {phase} device worker s{d['stage']}w{d['worker']}: "
            f"platform={d['platform']} kind={d['kind']} count={d['count']} "
            f"lower_s={d['lower_s']:.3f} compile_s={d['compile_s']:.3f} "
            f"compiles={d['compiles']} "
            f"dispatches={d['dispatches']} "
            f"rows_per_dispatch={events / max(d['dispatches'], 1):.1f}")


def phase_a(engine, plan, events, ref) -> list:
    t0 = time.perf_counter()
    result = engine.run(plan, source(events), drain_timeout=300.0)
    wall = time.perf_counter() - t0
    if result.outputs != ref:
        bad = next((i for i, (g, w) in enumerate(zip(result.outputs, ref))
                    if g != w), min(len(result.outputs), len(ref)))
        raise AssertionError(
            f"phase A: egress differs from the reference at row {bad} "
            f"({len(result.outputs)} rows out, {len(ref)} expected)"
        )
    log(f"phase A (stream, batch): pass  events={len(ref)} wall_s={wall:.3f}")
    report_devices("A", result.devices, len(ref))
    return result.devices


def phase_b(engine, plan, events, ref) -> list:
    n = len(events)
    bounds = [0] + [int(n * f) for f in (0.2, 0.45, 0.8)] + [n]
    t0 = time.perf_counter()
    got: list = []
    session = engine.open(plan)
    with session:
        for w, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
            session.push(source(events[lo:hi]))
            got.extend(session.results(max_items=hi - lo, timeout=300.0))
            if got != ref[:hi]:
                raise AssertionError(
                    f"phase B: after window {w} ({hi} events pushed) the "
                    f"results ({len(got)} rows) are not the reference prefix"
                )
        session.close(drain_timeout=300.0)
    wall = time.perf_counter() - t0
    devices = session.stats()["devices"]
    log(f"phase B (stream, session): pass  windows={len(bounds) - 1} "
        f"events={n} wall_s={wall:.3f}")
    report_devices("B", devices, n)
    return devices


# ------------------------------------------------------------------- model
def phase_c(cfg, seed: int) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer
    from repro.models.common import init_params
    from repro.serve.engine import OrderedServingEngine

    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lengths = [32, 64] * (REQUESTS // 2)  # two prompt shapes: two compiles
    budgets = [int(b) for b in rng.integers(4, 17, size=REQUESTS)]
    prompts = [rng.integers(0, cfg.vocab_size, size=s, dtype=np.int32)
               for s in lengths]
    server = OrderedServingEngine(cfg, params, max_slots=4, max_len=96)
    serials = [server.submit(p, max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
    done = server.run_to_completion()
    if [c.serial for c in done] != serials:
        raise AssertionError(
            f"phase C: completions {[c.serial for c in done]} are not in "
            f"submission order {serials}"
        )
    for c, b in zip(done, budgets):
        if not 1 <= len(c.tokens) <= b:
            raise AssertionError(
                f"phase C: request {c.serial} returned {len(c.tokens)} "
                f"tokens for a budget of {b}"
            )
    served = time.perf_counter() - t0

    got = np.asarray(server.prefill_logits(prompts[0]), np.float32)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(
            lambda p, t: transformer.forward_train(cfg32, p, t)
        )(params32, jnp.asarray(prompts[0])[None])
    want = np.asarray(logits[0, -1, :cfg.vocab_size], np.float32)
    got = got[:cfg.vocab_size]
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not np.isfinite(got).all() or rel > LOGITS_REL_L2:
        raise AssertionError(
            f"phase C: prefill logits differ from the float32 reference "
            f"(relative L2 error {rel:.4f} > {LOGITS_REL_L2})"
        )
    wall = time.perf_counter() - t0
    log(f"phase C (model server, {cfg.name}): pass  requests={REQUESTS} "
        f"tokens={sum(len(c.tokens) for c in done)} serve_wall_s={served:.3f} "
        f"wall_s={wall:.3f} logits_rel_l2={rel:.5f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--events", type=int, default=2_000_000)
    ap.add_argument("--device-batch", type=int, default=65_536)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    engine, plan = stream_plan(args.device_batch)
    t0 = time.perf_counter()
    events = make_events(args.events, args.seed)
    ref = reference(events)
    log(f"setup: {args.events} events, reference built in "
        f"{time.perf_counter() - t0:.3f}s")
    devices = phase_a(engine, plan, events, ref)
    devices += phase_b(engine, plan, events, ref)
    off_chip = sorted({d["platform"] for d in devices} - {"tpu"})
    if off_chip:
        raise RuntimeError(
            f"device workers ran on {off_chip}, not a TPU: no chip here"
        )
    # phase C: the first jax backend of this process, and the chip's owner
    import jax

    from repro.columnar import configure_compile_cache
    from repro.configs.olmo_1b import CONFIG

    cache = configure_compile_cache()
    chips = jax.devices()
    if chips[0].platform != "tpu":
        raise RuntimeError(f"jax found no TPU (platform {chips[0].platform})")
    log(f"phase C: jax devices {chips}, compile cache {cache}")
    phase_c(CONFIG, args.seed)
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(chips)}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
