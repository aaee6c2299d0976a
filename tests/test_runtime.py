"""The one platform check, Pallas interpret mode and the compile cache."""
import jax
import numpy as np

from repro import runtime
from repro.kernels.modmatmul import modmatmul
from repro.mpc.field import P_DEFAULT


def test_pallas_interpret_follows_the_platform(monkeypatch):
    assert runtime.pallas_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    assert runtime.pallas_interpret() is False
    monkeypatch.setattr(runtime, "on_tpu", lambda: False)
    assert runtime.pallas_interpret() is True


def test_kernel_interpret_defaults_to_the_platform():
    """No interpret argument: the kernel asks runtime (interpreted here)."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, P_DEFAULT, (8, 12))
    b = rng.integers(0, P_DEFAULT, (12, 8))
    want = np.array((a.astype(object) @ b.astype(object)) % P_DEFAULT,
                    np.int64)
    np.testing.assert_array_equal(np.asarray(modmatmul(a, b, p=P_DEFAULT)),
                                  want)


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "e"))
        assert runtime.use_compile_cache(str(tmp_path)) == str(tmp_path / "e")
        assert jax.config.jax_compilation_cache_dir == was   # JAX reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = runtime.use_compile_cache(str(tmp_path))
        assert got == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
