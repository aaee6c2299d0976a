"""Compile the served path for a described TPU v5e chip, with no chip here.

XLA:TPU refuses what the CPU backend accepts: an int64 ``dot_general``
(``UNIMPLEMENTED: While rewriting computation to not contain X64 element
types``) or a Pallas kernel with an int64 accumulator.  These tests lower
and compile every ``ProtocolStages`` program at ``s = t = 2, z = 2,
m = 2048`` for both primes, and the ``ShardedCMPC`` step on a 2×2 mesh,
for a ``v5e:2x2`` topology that is described, not attached.  A compile
that passes is not a chip run: it proves only that the chip's compiler
accepts the programs.

The topology is described inside a fixture (never at import), so that
under pytest-xdist only the worker given this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.mpc import MPCSpec
from repro.mpc.field import Field, P_DEFAULT, P_MERSENNE31
from repro.mpc.secure_matmul import ShardedCMPC

PRIMES = [P_DEFAULT, P_MERSENNE31]
STAGES = ["encode", "worker_compute", "exchange", "decode", "fused", "tags"]
M = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(p):
    return MPCSpec(s=2, t=2, z=2, field=Field(p))


def _no_wide_dot(lowered) -> None:
    """The served path's contractions are int8×int8→int32 only."""
    text = lowered.as_text()
    dots = [ln for ln in text.splitlines()
            if re.search(r"dot_general|convolution", ln)]
    assert dots, "expected at least one contraction"
    for ln in dots:
        assert not re.search(r"x(i64|f64)>", ln.split("->")[0]), ln


def _stage_args(plan, stage, sharding):
    """Shapes (not arrays) of one stage's arguments, on one described chip."""
    n, t2z = plan.n_workers, plan.recovery_threshold
    mt, ms = plan.m // plan.t, plan.m // plan.s

    def shp(shape, dtype=jnp.int64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    key = shp((2,), jnp.uint32)
    i_pts = shp((n, mt, mt))
    return {
        "encode": (shp((plan.m, plan.m)), shp((plan.m, plan.m)), key),
        "worker_compute": (shp((n, mt, ms)), shp((n, ms, mt))),
        "exchange": (i_pts, key),
        "decode": (i_pts, shp((t2z,)), shp((plan.t ** 2, t2z))),
        "fused": (shp((plan.m, plan.m)), shp((plan.m, plan.m)), key),
        "tags": (i_pts, shp(()), shp((n,)), shp((mt * mt,))),
    }[stage]


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("p", PRIMES)
def test_stage_compiles_for_v5e(one_chip, p, stage):
    plan = _spec(p).protocol(M).plan
    fn = getattr(plan.stages(), stage)
    lowered = fn.lower(*_stage_args(plan, stage, one_chip))
    _no_wide_dot(lowered)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30   # one v5e chip's HBM


@pytest.mark.parametrize("p", PRIMES)
def test_sharded_step_compiles_for_v5e_2x2(topo, p):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    runner = ShardedCMPC.from_spec(_spec(p), mesh, m=M)
    pr = runner.proto
    mt, ms = pr.m // pr.t, pr.m // pr.s
    k = pr.s * pr.t + pr.z

    def shp(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.int64,
                                    sharding=NamedSharding(mesh, spec))

    lowered = runner.build_step().lower(
        shp((k, mt, ms), P()), shp((k, ms, mt), P()),
        shp((runner.n_pad, pr.z, mt, mt), P("model")))
    _no_wide_dot(lowered)
    compiled = lowered.compile()
    # the phase-2 exchange is a cross-chip reduction (XLA:TPU may emit the
    # reduce-scatter as an all-reduce)
    assert re.search(r"reduce-scatter|all-reduce", compiled.as_text())


def test_pallas_mode_refused_on_tpu(monkeypatch):
    """mode="pallas" names Mosaic's int64-accumulator refusal on TPU."""
    from repro import runtime

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    proto = _spec(P_DEFAULT).protocol(8)
    a = np.ones((8, 8), np.int64)
    with pytest.raises(NotImplementedError, match="32-bit"):
        proto.run(a, a, jax.random.PRNGKey(0), mode="pallas")
    with pytest.raises(NotImplementedError, match="32-bit"):
        proto.phase2_compute(np.ones((17, 4, 4), np.int64),
                             np.ones((17, 4, 4), np.int64), use_kernel=True)
