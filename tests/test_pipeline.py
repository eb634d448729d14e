"""Pipeline parallelism: numerical equivalence with sequential execution,
gradient flow, and the multi-device sharded path (subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.pipeline import (
    PipelineConfig,
    merge_microbatches,
    pipeline_forward,
    pipeline_stats,
    split_microbatches,
    stack_stage_params,
)


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _setup(S=4, M=8, mb=2, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), S * 2 + 1)
    per_stage = tuple(
        {"w": jax.random.normal(ks[2 * i], (d, d)) * 0.3,
         "b": jax.random.normal(ks[2 * i + 1], (d,)) * 0.1}
        for i in range(S))
    x = jax.random.normal(ks[-1], (M * mb, d))
    return per_stage, x


def _sequential(per_stage, x):
    for p in per_stage:
        x = _stage_fn(p, x)
    return x


@pytest.mark.parametrize("S,M", [(2, 4), (4, 8), (3, 3)])
def test_pipeline_matches_sequential(S, M):
    per_stage, x = _setup(S=S, M=M)
    ref = _sequential(per_stage, x)
    cfg = PipelineConfig(n_stages=S, n_microbatches=M)
    out = merge_microbatches(pipeline_forward(
        _stage_fn, stack_stage_params(per_stage), split_microbatches(x, M),
        cfg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_gradients_match():
    per_stage, x = _setup(S=3, M=6, mb=2)
    stacked = stack_stage_params(per_stage)
    cfg = PipelineConfig(n_stages=3, n_microbatches=6)

    def loss_pipe(sp):
        out = pipeline_forward(_stage_fn, sp, split_microbatches(x, 6), cfg)
        return (merge_microbatches(out) ** 2).sum()

    def loss_seq(per):
        return (_sequential(per, x) ** 2).sum()

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = stack_stage_params(tuple(
        jax.tree_util.tree_map(lambda l, i=i: l, g)
        for i, g in enumerate(jax.grad(loss_seq)(per_stage))))
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_bubble_fraction():
    st = pipeline_stats(PipelineConfig(n_stages=4, n_microbatches=12))
    assert st["ticks"] == 15
    assert st["bubble_fraction"] == pytest.approx(3 / 15)


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.distributed.pipeline import (PipelineConfig, pipeline_forward,
    split_microbatches, merge_microbatches, stack_stage_params)

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

ks = jax.random.split(jax.random.key(0), 9)
per_stage = tuple({"w": jax.random.normal(ks[2*i], (16, 16)) * 0.3,
                   "b": jax.random.normal(ks[2*i+1], (16,)) * 0.1}
                  for i in range(4))
x = jax.random.normal(ks[-1], (16, 16))
ref = x
for p in per_stage:
    ref = stage_fn(p, ref)

# GSPMD-style (Auto) axes: pipeline_forward leaves placement to the
# compiler, which jax.make_mesh's default Explicit axes do not
mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
stacked = jax.device_put(stack_stage_params(per_stage),
                         NamedSharding(mesh, P("stage")))
cfg = PipelineConfig(n_stages=4, n_microbatches=8)
with mesh:
    out = jax.jit(lambda sp, mb: pipeline_forward(stage_fn, sp, mb, cfg))(
        stacked, split_microbatches(x, 8))
err = float(jnp.abs(merge_microbatches(out) - ref).max())
print("ERR=" + json.dumps(err))
assert err < 1e-4
"""


def test_pipeline_sharded_over_stage_axis():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "ERR=" in r.stdout
