"""Named scopes on the stage programs and the field GEMM, and the sharded
runner's byte counters.

The scopes are metadata of the ops: the op-name paths of the lowered HLO
must hold each stage's scope and the field GEMM's, and the programs with
the scopes taken out must compile to the same HLO, metadata aside.
"""
import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.mpc import MPCSpec, connect
from repro.mpc.planner import _build_stages

GEMM = {"field_gemm", "field_gemm.split", "field_gemm.dot",
        "field_gemm.recombine"}
STAGES = {"mpc.encode", "mpc.worker_compute", "mpc.exchange", "mpc.decode"}


def op_scopes(ir: str) -> set:
    """Every segment of every op-name path: the ``loc("...")`` names of
    the lowered module's debug info, or the ``op_name`` of HLO text."""
    return {seg for path in re.findall(r'(?:loc\(|op_name=)"([^"]*)"', ir)
            for seg in path.split("/")}


def without_metadata(hlo: str) -> str:
    """Compiled HLO text without op metadata and source-location tables."""
    return re.sub(r", metadata=\{[^}]*\}", "", hlo.split("\nFileNames")[0])


@pytest.fixture(scope="module")
def plan():
    return MPCSpec(s=2, t=2, z=2).plan(8)


def stage_args(plan, name):
    p, n, mt = plan.p, plan.n_workers, plan.m // plan.t
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, p, (plan.m, plan.m)))
    key = jax.random.PRNGKey(7)
    i_pts = jnp.asarray(rng.integers(0, p, (n, mt, mt)))
    return {
        "fused": (a, a.T, key),
        "front": (a, a.T, key),
        "decode": (i_pts, jnp.arange(plan.recovery_threshold),
                   jnp.asarray(plan.decode_rows)),
        "tags": (i_pts, jnp.int64(5), jnp.arange(n, dtype=jnp.int64),
                 jnp.asarray(rng.integers(0, p, (mt, mt)))),
    }[name]


SCOPES = {"fused": STAGES | GEMM,
          "front": {"mpc.encode", "mpc.worker_compute", "mpc.exchange"} | GEMM,
          "decode": {"mpc.decode"} | GEMM,
          "tags": {"mpc.tags"} | GEMM}


def lowered(stages, plan, name):
    return getattr(stages, name).lower(*stage_args(plan, name))


def compiled(stages, plan, name) -> str:
    return lowered(stages, plan, name).compile().as_text()


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_stage_ops_carry_their_scopes(plan, name):
    ir = lowered(_build_stages(plan), plan, name).as_text(debug_info=True)
    found = op_scopes(ir)
    assert SCOPES[name] <= found, SCOPES[name] - found
    others = STAGES - SCOPES[name]
    assert not others & found, others & found


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_scopes_change_only_metadata(plan, name, monkeypatch):
    scoped = compiled(_build_stages(plan), plan, name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled(_build_stages(plan), plan, name)
    assert not (STAGES | GEMM) & op_scopes(bare)
    assert without_metadata(scoped) == without_metadata(bare)


def test_scoped_product_is_exact(plan):
    rng = np.random.default_rng(11)
    p = plan.p
    a = rng.integers(0, p, (plan.m, plan.m))
    b = rng.integers(0, p, (plan.m, plan.m))
    y = plan.stages().fused(jnp.asarray(a).T, jnp.asarray(b),
                            jax.random.PRNGKey(1))
    want = (a.astype(object) @ b.astype(object)) % p
    np.testing.assert_array_equal(np.asarray(y), want.astype(np.int64))


@pytest.mark.parametrize("backend", ["local", "batched"])
def test_host_bytes_is_zero_off_the_mesh(backend):
    sess = connect(MPCSpec(s=2, t=2, z=2, m=8), backend=backend)
    sess.matmul(np.ones((8, 16)), np.ones((16, 8)))
    assert sess.stats["host_bytes"] == 0
    assert sess.backend.scheduler_stats()["host_bytes"] == 0


SHARDED = textwrap.dedent(
    """
    import contextlib, json, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.mpc import AGECMPCProtocol
    from repro.mpc.secure_matmul import ShardedCMPC

    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    sh = ShardedCMPC(proto, jax.make_mesh((4,), ("model",)), "model")
    e = proto.t * proto.s + proto.z
    args = (jnp.zeros((e, 4, 4), jnp.int64), jnp.zeros((e, 4, 4), jnp.int64),
            jnp.zeros((sh.n_pad, proto.z, 4, 4), jnp.int64))
    low = sh.build_step().lower(*args)
    ir, scoped = low.as_text(debug_info=True), low.compile().as_text()
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    bare = sh.build_step().lower(*args).compile().as_text()
    jax.named_scope = real

    def strip(t):
        return re.sub(r", metadata=\\{[^}]*\\}", "", t.split("\\nFileNames")[0])

    scopes = sorted({seg for path in re.findall(r'loc\\("([^"]*)"', ir)
                     for seg in path.split("/")})
    rng = np.random.default_rng(0)
    p = proto.field.p
    a = rng.integers(0, p, (8, 8))
    b = rng.integers(0, p, (8, 8))
    y = sh.run(a, b, jax.random.PRNGKey(0))
    want = (a.astype(object).T @ b.astype(object)) % p
    print(json.dumps({"scopes": scopes, "same": strip(scoped) == strip(bare),
                      "exact": bool(np.array_equal(np.asarray(y), want)),
                      "counters": sh.counters}))
    """
)


def test_sharded_step_scopes_and_host_bytes():
    import json

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    want = {"mpc.encode", "mpc.worker_compute", "mpc.exchange",
            "field_gemm.dot"}
    assert want <= set(out["scopes"]), want - set(out["scopes"])
    assert out["same"] and out["exact"]
    # one block: nothing through the host; of the quorum's rows 0-5 only
    # row 5 lies off the first of the four chips (5 workers each), int64
    assert out["counters"] == {"host_bytes": 0,
                               "mesh_bytes": 1 * (8 // 2) ** 2 * 8}
