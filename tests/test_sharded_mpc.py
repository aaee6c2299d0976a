"""Distributed (shard_map) CMPC runner — runs in a subprocess with 8 (or 4)
forced host devices so the main pytest process keeps seeing exactly 1 CPU
device."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, re
    from collections import Counter
    from repro.mpc import AGECMPCProtocol
    from repro.mpc.secure_matmul import ShardedCMPC, secure_matmul

    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    mesh = jax.make_mesh((8,), ("model",))
    sh = ShardedCMPC(proto, mesh, "model")
    assert sh.n_pad % 8 == 0 and sh.n_pad >= proto.n_workers

    rng = np.random.default_rng(0); p = proto.field.p
    A = rng.integers(0, p, (8, 8)); B = rng.integers(0, p, (8, 8))
    y = sh.run(A, B, jax.random.PRNGKey(0))
    want = np.array((A.astype(object).T @ B.astype(object)) % p, np.int64)
    assert np.array_equal(np.asarray(y), want), "sharded != reference"

    Af = rng.standard_normal((8, 8)).astype(np.float32)
    Bf = rng.standard_normal((8, 8)).astype(np.float32)
    out = secure_matmul(Af, Bf, s=2, t=2, z=2, mesh=mesh)
    assert float(np.abs(out - Af.T @ Bf).max()) < 0.05, "facade error too big"

    # phase-2 exchange must be exactly one reduce-scatter on the worker axis
    import jax.numpy as jnp
    step = sh.build_step()
    ta = jnp.zeros((proto.t*proto.s + proto.z, 4, 4), jnp.int64)
    tb = jnp.zeros((proto.t*proto.s + proto.z, 4, 4), jnp.int64)
    mk = jnp.zeros((sh.n_pad, proto.z, 4, 4), jnp.int64)
    txt = jax.jit(step).lower(ta, tb, mk).compile().as_text()
    colls = Counter(re.findall(
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
        txt))
    assert colls.get("reduce-scatter", 0) >= 1, colls
    assert colls.get("all-gather", 0) == 0, colls
    print("SHARDED_OK")
    """
)


def test_sharded_runner_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env,
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_OK" in res.stdout


OPT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.mpc import AGECMPCProtocol
    from repro.mpc.secure_matmul import ShardedCMPC

    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(3); p = proto.field.p
    A = rng.integers(0, p, (8, 8)); B = rng.integers(0, p, (8, 8))
    want = np.array((A.astype(object).T @ B.astype(object)) % p, np.int64)
    # all optimization-knob combinations stay exact (§Perf A1/A2b)
    for kw in [dict(wire_dtype="int32"), dict(prg_masks=True),
               dict(wire_dtype="int32", prg_masks=True)]:
        sh = ShardedCMPC(proto, mesh, "model", **kw)
        y = sh.run(A, B, jax.random.PRNGKey(1))
        assert np.array_equal(np.asarray(y), want), kw
    print("OPT_VARIANTS_OK")
    """
)


def test_optimized_variants_exact_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", OPT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OPT_VARIANTS_OK" in res.stdout


QUORUM_SCRIPT = textwrap.dedent(
    """
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.mpc import AGECMPCProtocol
    from repro.mpc.secure_matmul import ShardedCMPC

    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    n = proto.n_workers
    rng = np.random.default_rng(5); p = proto.field.p
    A = rng.integers(0, p, (8, 8)); B = rng.integers(0, p, (8, 8))
    want = np.array((A.astype(object).T @ B.astype(object)) % p, np.int64)
    line = ((4,), ("model",))
    grid = ((2, 2), ("data", "model"))    # workers replicated over "data"
    cases = {"default": (line, None),
             "drop-0-2": (line, np.arange(n) >= 3),
             "scattered": (line, np.isin(np.arange(n), [1, 4, 7, 10, 13, 16])),
             "replicated": (grid, None)}
    out = {}
    for name, (shape, mask) in cases.items():
        sh = ShardedCMPC(proto, jax.make_mesh(*shape), "model")
        y = sh.run(A, B, jax.random.PRNGKey(2), survivors=mask)
        out[name] = {
            "exact": bool(np.array_equal(np.asarray(y), want)),
            "on_decode_device": y.committed
                and y.devices() == {sh.decode_device},
            "idx": proto.survivor_prefix(mask).tolist(),
            "per_device": sh.n_pad // sh.axis_size,
            "counters": sh.counters,
        }
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def quorum_runs():
    import json

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", QUORUM_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["default", "drop-0-2", "scattered",
                                  "replicated"])
def test_quorum_rows_stay_on_the_chips(quorum_runs, case):
    """The decode reads only the quorum's rows, moved chip to chip: exact
    for any survivor set, nothing through the host, and the bytes of the
    rows held off the decode device (the mesh's first) counted; where the
    workers are replicated, the decode device's own copy is read."""
    run = quorum_runs[case]
    assert run["exact"] and run["on_decode_device"]
    moved = sum(r // run["per_device"] != 0 for r in run["idx"])
    assert moved == {"default": 1, "drop-0-2": 4, "scattered": 4,
                     "replicated": 0}[case]
    assert run["counters"] == {"host_bytes": 0,
                               "mesh_bytes": moved * (8 // 2) ** 2 * 8}
