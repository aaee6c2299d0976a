"""Substrate tests: data determinism, checkpoint atomicity/resume, AdamW +
WSD behavior, gradient compression, sharding rules."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticTokens
from repro.optim.adamw import AdamW, global_norm
from repro.optim.schedule import wsd
from repro.parallel.sharding import spec_for
from jax.sharding import PartitionSpec as P


# ------------------------------------------------------------------- data --
def test_data_deterministic_and_shardable():
    ds = SyntheticTokens(vocab=1000, seq_len=16, global_batch=8, seed=3)
    b1, b2 = ds.batch_np(5), ds.batch_np(5)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    # host shard == slice of global batch (elastic restart property)
    sh = ds.batch_np(5, lo=2, hi=6)
    assert np.array_equal(b1["tokens"][2:6], sh["tokens"])
    # next-token alignment
    assert np.array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])
    # different steps differ
    assert not np.array_equal(b1["tokens"], ds.batch_np(6)["tokens"])
    assert (b1["tokens"] < 1000).all() and (b1["tokens"] >= 0).all()


# ------------------------------------------------------------- checkpoint --
def test_checkpoint_atomic_commit_and_resume():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        state = {"w": jnp.arange(6.0).reshape(2, 3), "step": jnp.int32(7)}
        mgr.save(1, state)
        mgr.save(2, state)
        mgr.save(3, state)  # keep=2 -> step 1 garbage-collected
        assert mgr.all_steps() == [2, 3]
        # a torn write (tmp dir without manifest) is invisible
        os.makedirs(os.path.join(d, "step_00000009.tmp"))
        assert mgr.latest_step() == 3
        got = mgr.restore(3, state)
        assert np.array_equal(np.asarray(got["w"]), np.asarray(state["w"]))
        assert int(got["step"]) == 7


def test_checkpoint_async_then_restore():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        state = {"a": jnp.ones((4, 4))}
        mgr.save_async(10, state)
        mgr.wait()
        r = mgr.restore(10, state)
        np.testing.assert_array_equal(np.asarray(r["a"]), np.ones((4, 4)))


# ------------------------------------------------------------------ optim --
def test_adamw_descends_quadratic():
    opt = AdamW(weight_decay=0.0, clip_norm=None)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}  # d/dx x²
        params, state, _ = opt.update(grads, state, params, lr=0.05)
    assert float(jnp.abs(params["x"]).max()) < 0.5


def test_adamw_clipping():
    opt = AdamW(clip_norm=1.0, weight_decay=0.0)
    params = {"x": jnp.zeros(3)}
    state = opt.init(params)
    _, _, gnorm = opt.update({"x": jnp.full(3, 100.0)}, state, params, 1e-3)
    assert float(gnorm) == pytest.approx(np.sqrt(3) * 100, rel=1e-5)


def test_wsd_schedule_shape():
    def lr(s):
        return float(wsd(s, peak_lr=1.0, warmup=10, stable=20, decay=10,
                         floor=0.1))

    assert lr(0) == 0.0
    assert lr(5) == pytest.approx(0.5)
    assert lr(10) == pytest.approx(1.0)
    assert lr(25) == pytest.approx(1.0)      # stable plateau
    assert 0.1 < lr(35) < 1.0                # decaying
    assert lr(40) == pytest.approx(0.1)      # floor
    assert lr(100) == pytest.approx(0.1)


def test_bf16_optimizer_state():
    opt = AdamW(state_dtype="bfloat16")
    params = {"x": jnp.ones(4, jnp.bfloat16)}
    st = opt.init(params)
    assert st.mu["x"].dtype == jnp.bfloat16
    p2, st2, _ = opt.update({"x": jnp.ones(4)}, st, params, 1e-2)
    assert st2.nu["x"].dtype == jnp.bfloat16
    assert p2["x"].dtype == jnp.bfloat16


# --------------------------------------------------------------- sharding --
def test_spec_for_divisibility_guard():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    m = FakeMesh()
    # divisible dims shard; non-divisible fall back to replication
    assert spec_for((256, 4096), ("batch", None), m) == P("data", None)
    assert spec_for((15, 64), ("heads", None), m) == P(None, None)
    assert spec_for((32, 64), ("heads", None), m) == P("model", None)
    # one mesh axis never used twice
    assert spec_for((32, 32), ("heads", "ffn"), m) == P("model", None)


def test_compressed_psum_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.compressed import compressed_psum

        mesh = jax.make_mesh((4,), ("pod",))

        def f(g):
            out, err = compressed_psum({"g": g}, "pod")
            return out["g"], err["g"]

        g = jnp.arange(32.0).reshape(4, 8) / 7.3
        fm = jax.jit(shard_map(f, mesh=mesh, in_specs=P("pod", None),
                               out_specs=(P("pod", None), P("pod", None))))
        out, err = fm(g)
        # mean over 4 shards, int8-quantized: close to true mean
        true = np.repeat(np.asarray(g).mean(0, keepdims=True), 4, 0)
        rel = np.abs(np.asarray(out) - true).max() / (np.abs(true).max())
        assert rel < 0.02, rel
        print("COMPRESSED_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "COMPRESSED_OK" in res.stdout
