"""Multi-device correctness via subprocess (8 host placeholder devices):
sharded train step must match the single-device trajectory."""
import json
import os
import subprocess
import sys


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.registry import get_smoke_config
from repro.distributed.sharding import (init_params, make_rules,
                                        activation_sharding, param_shardings)
from repro.models import api
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step
from repro.data.pipeline import pipeline_for_model

cfg = get_smoke_config("granite-3-2b")
pipe = pipeline_for_model(cfg, global_batch=8, seq_len=32, seed=0)
opt = AdamWConfig(lr=1e-3, total_steps=10, warmup=2)
params = init_params(api.param_specs(cfg), jax.random.key(0))
state = init_train_state(cfg, opt, params)
step = make_train_step(cfg, opt)

# GSPMD-style (Auto) axes: the sharding rules constrain, the compiler
# places; jax.make_mesh's default Explicit axes would type every gather
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rules = make_rules(fsdp=True)
with mesh:
    with activation_sharding(mesh, rules):
        jstep = jax.jit(step)
        losses = []
        for i in range(5):
            batch = pipe.batch_at(i)
            batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
            state, m = jstep(state, batch)
            losses.append(float(m["loss"]))
print("LOSSES=" + json.dumps(losses))
assert len(set(str(d) for l in jax.tree_util.tree_leaves(state)
                for d in l.devices())) >= 2, "state not distributed"
"""

SINGLE = SCRIPT.replace(
    'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"',
    "").replace('jax.make_mesh((4, 2), ("data", "model")',
                'jax.make_mesh((1, 1), ("data", "model")').replace(
    'assert len(set(str(d)', 'assert True or len(set(str(d)')


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    for line in r.stdout.splitlines():
        if line.startswith("LOSSES="):
            return json.loads(line[len("LOSSES="):])
    raise AssertionError(f"no losses in output: {r.stdout[-500:]}")


def test_sharded_training_matches_single_device():
    multi = _run(SCRIPT)
    single = _run(SINGLE)
    for a, b in zip(multi, single):
        assert abs(a - b) / max(abs(b), 1e-6) < 5e-3, (multi, single)
