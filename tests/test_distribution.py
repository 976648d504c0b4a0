"""Distribution tests on a small forced-host-device mesh (subprocess so the
main test process keeps its single CPU device)."""
import json
import os
import subprocess
import sys

import pytest

pytest.importorskip(
    "jax", reason="distribution tests fork a jax host-device mesh subprocess"
)

_SMALL_MESH_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses
from repro.configs import smoke_config, SHAPES
from repro.launch.mesh import make_test_mesh
from repro.models.common import abstract_params, init_params, param_pspecs
from repro.sharding.context import use_mesh
from repro.sharding.partitioning import named_sanitized, batch_spec
from repro.train.optimizer import OptConfig, abstract_opt_state
from repro.train import train_step as ts

results = {}

# --- lower+compile a reduced train step on the (2,4) test mesh
cfg = smoke_config("olmo-1b")
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
mesh = make_test_mesh()
ocfg = OptConfig()
with mesh, use_mesh(mesh):
    step = ts.make_train_step(cfg, ocfg)
    ins, outs = ts.train_step_shardings(cfg, ocfg, mesh, shape)
    ap = abstract_params(cfg)
    args = (ap, abstract_opt_state(ocfg, ap), ts.abstract_train_batch(cfg, shape))
    compiled = jax.jit(step, in_shardings=ins, out_shardings=outs,
                       donate_argnums=(0, 1)).lower(*args).compile()
results["train_compiles"] = True
results["train_flops"] = compiled.cost_analysis().get("flops", 0)

# --- multi-pod test mesh (2,2,2): pod axis must shard
cfg2 = smoke_config("qwen2-moe-a2.7b")
mesh2 = make_test_mesh(multi_pod=True)
with mesh2, use_mesh(mesh2):
    step = ts.make_train_step(cfg2, ocfg)
    shape2 = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=8)
    ins, outs = ts.train_step_shardings(cfg2, ocfg, mesh2, shape2)
    ap = abstract_params(cfg2)
    args = (ap, abstract_opt_state(ocfg, ap), ts.abstract_train_batch(cfg2, shape2))
    compiled2 = jax.jit(step, in_shardings=ins, out_shardings=outs,
                        donate_argnums=(0, 1)).lower(*args).compile()
results["multipod_compiles"] = True

# --- REAL execution of a sharded train step on 8 devices (numerics parity)
cfg3 = smoke_config("olmo-1b")
params = init_params(cfg3, jax.random.PRNGKey(0))
import numpy as np
toks = jnp.asarray(np.random.RandomState(0).randint(0, cfg3.vocab_size, (8, 32)))
batch = {"tokens": toks, "labels": toks}
from repro.models.transformer import loss_fn
with mesh, use_mesh(mesh):
    pp = named_sanitized(mesh, param_pspecs(cfg3), abstract_params(cfg3))
    sparams = jax.device_put(params, pp)
    sbatch = jax.device_put(batch, NamedSharding(mesh, batch_spec(mesh, 8, 1)))
    loss_sharded, _ = jax.jit(lambda p, b: loss_fn(cfg3, p, b))(sparams, sbatch)
loss_single, _ = loss_fn(cfg3, params, batch)
results["loss_sharded"] = float(loss_sharded)
results["loss_single"] = float(loss_single)

# --- int8 error-feedback gradient psum over the pod axis (shard_map)
from repro.train.grad_compression import compress_allreduce_leaf
g = jnp.arange(16.0).reshape(2, 8) * 0.01  # (pod-sharded dim, payload)
err = jnp.zeros((2, 8))
def fn(gl, el):
    s, e = compress_allreduce_leaf(gl[0], el[0], "pod")
    return s[None], e[None]
with mesh2:
    summed, new_err = jax.shard_map(
        fn, mesh=mesh2, in_specs=(P("pod", None), P("pod", None)),
        out_specs=(P("pod", None), P("pod", None)), check_vma=False,
    )(g, err)
true_sum = g.sum(axis=0)
rel = float(jnp.linalg.norm(summed[0] - true_sum) / (jnp.linalg.norm(true_sum)))
results["compressed_psum_rel_err"] = rel
print("RESULTS:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def small_mesh_results():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", _SMALL_MESH_PROG],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


def test_train_step_compiles_on_mesh(small_mesh_results):
    assert small_mesh_results["train_compiles"]
    assert small_mesh_results["train_flops"] > 0


def test_multipod_mesh_compiles(small_mesh_results):
    assert small_mesh_results["multipod_compiles"]


def test_sharded_loss_matches_single_device(small_mesh_results):
    a = small_mesh_results["loss_sharded"]
    b = small_mesh_results["loss_single"]
    assert abs(a - b) / max(abs(b), 1e-6) < 5e-2, (a, b)


def test_compressed_psum_close(small_mesh_results):
    assert small_mesh_results["compressed_psum_rel_err"] < 0.02
