"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU compiler is installed alongside JAX, so the kernels of the main
path are lowered and compiled here for a ``v5e:2x2`` topology at the sizes
the planner really runs: the compiler refuses what the chip would refuse
(unsupported primitives, misaligned blocks, more VMEM than a kernel may
use), which interpret mode cannot show.  Nothing here runs.

The topology is described inside a module-scope fixture — never while a
module is imported — because only one process at a time may load the TPU
library; every test of that need lives in this one file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import partition, qn_sim
from repro.kernels.amva import kernel as amva_kernel
from repro.kernels.qn_event import kernel as qn_kernel

# the event budget of one Table-3 Q3 1000 GB lane (1560 maps, 1009 reduces)
EVENTS = qn_sim.padded_event_budget(1560, 1009, min_jobs=40, warmup_jobs=8)
HBM_BYTES = 16 * 2**30               # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # executables for a described chip can be written to the
        # persistent cache but never read back: keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lanes(n, sharding):
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding)
    # n_map, n_reduce, m_avg, r_avg, think_ms, slots_cap, seed, budget
    return (i32, i32, f32, f32, f32, i32, i32, i32)


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB of HBM"


def test_event_budget_is_the_papers_largest():
    assert EVENTS == 524_288


def test_amva_compiles_at_4096_points(one_chip):
    x = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    fwd = functools.partial(amva_kernel.amva_fwd, interpret=False)
    compiled = jax.jit(fwd).lower(x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes,max_slots,h_users,replay",
                         [(8, 128, 1, True), (128, 128, 5, False)])
def test_qn_event_compiles_at_524288_events(one_chip, lanes, max_slots,
                                            h_users, replay):
    samples = ()
    if replay:     # the paper's replayer mode: shared duration lists
        s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
        samples = (s, s)
    fwd = functools.partial(
        qn_kernel.qn_event_fwd, h_users=h_users, max_slots=max_slots,
        n_events=EVENTS, warmup_jobs=8, interpret=False)
    compiled = jax.jit(fwd).lower(*_lanes(lanes, one_chip),
                                  *samples).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)


def test_qn_event_builds_replay_draws_once_per_seed(one_chip):
    """Twelve lanes of two seeds (6 candidates x 2 replications): the
    compiled program draws one replay index table for the two seeds only
    (both lists have 2,048 entries, so they share it), copies the samples
    out of the lists in the ``qn_replay_lookup`` kernel, and broadcasts
    them to the lanes: no gather anywhere."""
    lanes, period = 12, 2
    s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    fwd = functools.partial(
        qn_kernel.qn_event_fwd, h_users=1, max_slots=128, n_events=EVENTS,
        warmup_jobs=8, interpret=False, seed_period=period)
    compiled = jax.jit(fwd).lower(*_lanes(lanes, one_chip), s, s).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "gather(" not in text
    lookup = [ln for ln in text.splitlines()
              if re.search(r"%qn_replay_lookup\S* = .* custom-call\(", ln)]
    assert len(lookup) == 1, lookup
    operands = re.search(r"operand_layout_constraints=\{(.*?\})\}",
                         lookup[0]).group(1)
    tables = [int(np.prod([int(d) for d in dims.split(",")]))
              for dims in re.findall(r"s32\[([\d,]+)\]", operands)]
    assert tables == [period * EVENTS], operands
    _fits_hbm(compiled)


def test_scan_reference_compiles_at_524288_events(one_chip):
    compiled = qn_sim._sim_batch_jit.lower(
        *_lanes(8, one_chip), None, None, h_users=1, max_slots=128,
        n_events=EVENTS, warmup_jobs=8).compile()
    _fits_hbm(compiled)


def test_sharded_round_compiles_over_four_chips(topo, monkeypatch):
    shards = 4
    mesh = Mesh(np.asarray(topo.devices[:shards]), ("lanes",))
    # steer the lane-sharding plane onto the described chips
    monkeypatch.setattr(partition, "_MESHES", {shards: mesh})
    monkeypatch.setattr(partition, "_CALLS", {})
    fwd = functools.partial(qn_kernel.qn_event_fwd, interpret=False)
    statics = dict(h_users=2, max_slots=32, n_events=65_536,
                   warmup_jobs=8)
    sharded = partition._sharded(fwd, shards, 8, 2,
                                 tuple(sorted(statics.items())))
    lanes = NamedSharding(mesh, PartitionSpec("lanes"))
    compiled = sharded.lower(*_lanes(shards * 16, lanes), None,
                             None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # lanes are independent: no collective between the chips
    assert "all-gather" not in text and "all-reduce" not in text
