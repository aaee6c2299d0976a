"""Fast-path correctness: Barrett/limb/batched kernels bit-exact vs the
reference oracles across (s,t,z) grids, odd shapes and both supported
primes; plan-cache hit/miss semantics; accumulation-window contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dep: deterministic fallback sweeps
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.kernels import ref
from repro.analysis.overflow import certified_limb_k
from repro.kernels.barrett import (
    barrett_params,
    field_matmul,
    matmul_folded,
    matmul_limbs,
    mod_p,
)
from repro.kernels.modmatmul import modmatmul, modmatmul_batched
from repro.kernels.polyeval import polyeval
from repro.mpc import (
    AGECMPCProtocol,
    build_plan,
    cache_clear,
    cache_info,
    get_plan,
)
from repro.mpc import lagrange as lag
from repro.mpc.field import (
    ACC_WINDOW,
    DEFAULT_FIELD,
    Field,
    P_DEFAULT,
    P_MERSENNE31,
    acc_window,
)
from repro.mpc.montgomery import mont_ctx

PRIMES = [P_DEFAULT, P_MERSENNE31]


def exact_matmul(a, b, p):
    return np.array(
        (np.asarray(a).astype(object) @ np.asarray(b).astype(object)) % p,
        dtype=np.int64)


def exact_ref(a, b, p):
    return np.array((a.astype(object).T @ b.astype(object)) % p,
                    dtype=np.int64)


# ------------------------------------------------------------ barrett mod_p


@pytest.mark.parametrize("p", PRIMES + [97])
def test_mod_p_matches_remainder(p):
    rng = np.random.default_rng(p)
    x = np.concatenate([
        rng.integers(0, 2**63 - 1, 4096, dtype=np.int64),
        np.array([0, 1, p - 1, p, p + 1, 2 * p, 2**62, 2**63 - 1], np.int64),
    ])
    got = np.asarray(mod_p(jnp.asarray(x), p))
    np.testing.assert_array_equal(got, x % p)


def test_barrett_params_pseudo_mersenne():
    assert barrett_params(P_DEFAULT) == (26, 5, 2)
    assert barrett_params(P_MERSENNE31) == (31, 1, 2)
    assert barrett_params(97) is None  # not pseudo-Mersenne: % fallback


# --------------------------------------------------- accumulation contract


def test_acc_window_is_the_single_source_of_truth():
    for p in PRIMES:
        w = acc_window(p)
        assert ACC_WINDOW[p] == w
        # exactness: w products + a < p accumulator fit int64 ...
        assert w * (p - 1) ** 2 + (p - 1) < 2**63
        # ... and w is maximal
        assert (w + 1) * (p - 1) ** 2 + (p - 1) >= 2**63
    assert acc_window(P_DEFAULT) == 2048  # the documented p = 2²⁶−5 value


def test_kernels_reject_oversized_bk():
    a = jnp.ones((8, 8), jnp.int64)
    with pytest.raises(ValueError, match="acc_window"):
        modmatmul(a, a, p=P_DEFAULT, bk=4096)
    with pytest.raises(ValueError, match="acc_window"):
        modmatmul_batched(a[None], a[None], p=P_DEFAULT, bk=4096)
    big = jnp.ones((4, acc_window(P_DEFAULT) + 1), jnp.int64)
    with pytest.raises(ValueError, match="acc_window"):
        polyeval(big, jnp.ones((acc_window(P_DEFAULT) + 1, 4), jnp.int64),
                 p=P_DEFAULT)


def test_kernel_default_bk_clamps_to_window():
    """Mersenne-31's window is 2: the default bk must clamp, not raise."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, P_MERSENNE31, (4, 6)), jnp.int64)
    b = jnp.asarray(rng.integers(0, P_MERSENNE31, (6, 4)), jnp.int64)
    got = modmatmul(a, b, p=P_MERSENNE31, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), exact_matmul(a, b, P_MERSENNE31))


# ------------------------------------------------------- folded/limb matmul


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("shape", [(7, 300, 5), (1, 1, 1), (33, 65, 17)])
def test_matmul_folded_exact(p, shape):
    m, k, n = shape
    rng = np.random.default_rng(m + k + n)
    a = rng.integers(0, p, (m, k))
    b = rng.integers(0, p, (k, n))
    got = np.asarray(matmul_folded(a, b, p=p, window=acc_window(p)))
    np.testing.assert_array_equal(got, exact_matmul(a, b, p))


@pytest.mark.parametrize("p", PRIMES)
def test_matmul_limbs_exact_incl_worst_case(p):
    rng = np.random.default_rng(3)
    a = rng.integers(0, p, (3, 9, 40))
    b = rng.integers(0, p, (3, 40, 11))
    got = np.asarray(matmul_limbs(a, b, p=p))
    want = np.stack([exact_matmul(a[i], b[i], p) for i in range(3)])
    np.testing.assert_array_equal(got, want)
    # worst case: every entry p-1 (max products, max carries)
    k = 257
    aw = np.full((4, k), p - 1)
    bw = np.full((k, 4), p - 1)
    got = np.asarray(matmul_limbs(aw, bw, p=p))
    np.testing.assert_array_equal(got, exact_matmul(aw, bw, p))


# ------------------------------------------------------ int8 limb field GEMM


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("shape", [(7, 300, 5), (1, 1, 1), (33, 65, 17),
                                   (2, 9, 40)])
def test_field_matmul_exact(p, shape):
    m, k, n = shape
    rng = np.random.default_rng(m + k + n)
    a = rng.integers(0, p, (m, k))
    b = rng.integers(0, p, (k, n))
    got = np.asarray(field_matmul(a, b, p=p))
    np.testing.assert_array_equal(got, exact_matmul(a, b, p))


@pytest.mark.parametrize("p", PRIMES)
def test_field_matmul_batched_and_broadcast(p):
    rng = np.random.default_rng(5)
    a = rng.integers(0, p, (3, 9, 40))
    b = rng.integers(0, p, (3, 40, 11))
    want = np.stack([exact_matmul(a[i], b[i], p) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(field_matmul(a, b, p=p)), want)
    want = np.stack([exact_matmul(a[i], b[0], p) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(field_matmul(a, b[0], p=p)),
                                  want)


@pytest.mark.parametrize("p", PRIMES)
@pytest.mark.parametrize("k", ["1", "257", "cert-1", "cert", "cert+1",
                               "2cert+3"])
def test_field_matmul_all_max_at_k_chunk_boundaries(p, k):
    """All-(p−1) operands (the largest limbs, diagonal sums and carries)
    on both sides of every K-chunk edge the int32 certificate sets, with
    the Toeplitz stack on either operand."""
    cert = certified_limb_k(p)
    k = {"1": 1, "257": 257, "cert-1": cert - 1, "cert": cert,
         "cert+1": cert + 1, "2cert+3": 2 * cert + 3}[k]
    a = np.full((2, k), p - 1)
    b = np.full((k, 3), p - 1)
    np.testing.assert_array_equal(np.asarray(field_matmul(a, b, p=p)),
                                  exact_matmul(a, b, p))
    np.testing.assert_array_equal(np.asarray(field_matmul(b.T, a.T, p=p)),
                                  exact_matmul(b.T, a.T, p))
    assert int(field_matmul(a, b, p=p)[0, 0]) == k * (p - 1) ** 2 % p


# ----------------------------------------------------------- batched kernel


@pytest.mark.parametrize(
    "w,m,k,n,bm,bn,bk",
    [
        (1, 8, 8, 8, 8, 8, 8),
        (3, 16, 300, 12, 8, 8, 128),    # k not block multiple
        (5, 33, 65, 17, 16, 16, 32),    # nothing aligned
        (2, 1, 7, 1, 8, 8, 8),          # degenerate
        (4, 64, 1024, 64, 32, 32, 512),  # multi K-fold
    ],
)
def test_modmatmul_batched_matches_oracle(w, m, k, n, bm, bn, bk):
    rng = np.random.default_rng(w * 10000 + m * 100 + k + n)
    a = jnp.asarray(rng.integers(0, P_DEFAULT, (w, m, k)), jnp.int64)
    b = jnp.asarray(rng.integers(0, P_DEFAULT, (w, k, n)), jnp.int64)
    got = modmatmul_batched(a, b, p=P_DEFAULT, bm=bm, bn=bn, bk=bk,
                            interpret=True)
    want = ref.modmatmul_batched_ref(a, b, p=P_DEFAULT)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(
    w=st.integers(1, 4),
    m=st.integers(1, 24),
    k=st.integers(1, 80),
    n=st.integers(1, 24),
    p=st.sampled_from(PRIMES),
    seed=st.integers(0, 2**31 - 1),
)
def test_modmatmul_batched_property(w, m, k, n, p, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, p, (w, m, k)), jnp.int64)
    b = jnp.asarray(rng.integers(0, p, (w, k, n)), jnp.int64)
    got = modmatmul_batched(a, b, p=p, bm=16, bn=16, interpret=True)
    want = np.stack([exact_matmul(a[i], b[i], p) for i in range(w)])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_polyeval_large_k_within_window():
    """K > 512 is fine now — the cap is the field window (2048)."""
    rng = np.random.default_rng(1)
    vand = jnp.asarray(rng.integers(0, P_DEFAULT, (6, 600)), jnp.int64)
    terms = jnp.asarray(rng.integers(0, P_DEFAULT, (600, 33)), jnp.int64)
    got = polyeval(vand, terms, p=P_DEFAULT, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  exact_matmul(vand, terms, P_DEFAULT))


# --------------------------------------------------- vectorized plan algebra


@pytest.mark.parametrize("p", PRIMES)
def test_vandermonde_and_inverse_match_reference(p):
    f = Field(p)
    rng = np.random.default_rng(p % 1000)
    alphas = rng.integers(1, p, 19)
    powers = rng.integers(0, 50, 23)
    np.testing.assert_array_equal(
        lag.vandermonde(f, alphas, powers),
        lag.vandermonde_ref(f, alphas, powers))
    tbl = lag.power_table(f, alphas, 50)
    np.testing.assert_array_equal(
        tbl, lag.vandermonde_ref(f, alphas, np.arange(51)))
    mat = rng.integers(0, p, (12, 12))
    try:
        want = lag.inv_mod_ref(f, mat)
    except np.linalg.LinAlgError:
        pytest.skip("random matrix singular (fine)")
    got = lag.inv_mod(f, mat)
    np.testing.assert_array_equal(got, want)
    eye = lag.matmul_mod(got, mat, p)
    np.testing.assert_array_equal(eye, np.eye(12, dtype=np.int64))


def test_montgomery_pow_matches_python_pow():
    ctx = mont_ctx(P_DEFAULT)
    rng = np.random.default_rng(0)
    bases = rng.integers(0, P_DEFAULT, 64)
    exps = rng.integers(0, 1000, 64)
    got = ctx.pow(bases, exps)
    want = np.array([pow(int(b), int(e), P_DEFAULT)
                     for b, e in zip(bases, exps, strict=True)], np.int64)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("scheme,s,t,z", [
    ("age", 2, 2, 2), ("age", 3, 2, 2), ("age", 2, 3, 3),
    ("entangled", 2, 2, 2), ("polydot", 2, 2, 2),
])
def test_plan_tables_bit_exact_vs_reference_build(scheme, s, t, z):
    m = s * t * 2
    fast = build_plan(scheme, s, t, z, None, DEFAULT_FIELD, m)
    slow = build_plan(scheme, s, t, z, None, DEFAULT_FIELD, m,
                      use_reference=True)
    for fld in ("alphas", "powers_h", "r_coeffs", "vand_a", "vand_b",
                "g_mix", "vand_g_secret", "decode_rows"):
        np.testing.assert_array_equal(
            getattr(fast, fld), getattr(slow, fld), err_msg=fld)


# ------------------------------------------------------- fused protocol run


@pytest.mark.parametrize(
    "s,t,z,m",
    [(2, 2, 2, 8), (1, 2, 1, 8), (2, 1, 2, 8), (3, 2, 2, 12),
     (2, 3, 3, 12), (1, 3, 2, 9), (4, 2, 1, 8)],
)
def test_fused_run_bit_exact(s, t, z, m):
    """run (fused default) == run_reference == the object-dtype oracle."""
    proto = AGECMPCProtocol(s=s, t=t, z=z, m=m)
    rng = np.random.default_rng(42 + s + t + z)
    a = rng.integers(0, proto.field.p, (m, m))
    b = rng.integers(0, proto.field.p, (m, m))
    key = jax.random.PRNGKey(s * 100 + t * 10 + z)
    want = exact_ref(a, b, proto.field.p)
    np.testing.assert_array_equal(np.asarray(proto.run(a, b, key)), want)
    np.testing.assert_array_equal(
        np.asarray(proto.run_reference(a, b, key)), want)


@pytest.mark.parametrize("scheme", ["age", "entangled", "polydot"])
def test_fused_run_all_schemes(scheme):
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8, scheme=scheme)
    rng = np.random.default_rng(17)
    a = rng.integers(0, proto.field.p, (8, 8))
    b = rng.integers(0, proto.field.p, (8, 8))
    y = proto.run(a, b, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(y),
                                  exact_ref(a, b, proto.field.p))


def test_fused_run_mersenne31():
    f = Field(P_MERSENNE31)
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8, field=f)
    rng = np.random.default_rng(31)
    a = rng.integers(0, f.p, (8, 8))
    b = rng.integers(0, f.p, (8, 8))
    y = proto.run(a, b, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(y), exact_ref(a, b, f.p))


def test_pallas_mode_bit_exact():
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    rng = np.random.default_rng(7)
    a = rng.integers(0, proto.field.p, (8, 8))
    b = rng.integers(0, proto.field.p, (8, 8))
    key = jax.random.PRNGKey(2)
    y = proto.run(a, b, key, mode="pallas")
    np.testing.assert_array_equal(np.asarray(y),
                                  exact_ref(a, b, proto.field.p))


@settings(max_examples=6, deadline=None)
@given(
    s=st.sampled_from([1, 2, 3]),
    t=st.sampled_from([1, 2, 3]),
    z=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_run_property(s, t, z, seed):
    if s == 1 and t == 1:
        s = 2
    m = s * t * 2
    proto = AGECMPCProtocol(s=s, t=t, z=z, m=m)
    rng = np.random.default_rng(seed)
    a = rng.integers(0, proto.field.p, (m, m))
    b = rng.integers(0, proto.field.p, (m, m))
    y = proto.run(a, b, jax.random.PRNGKey(seed % 2**31))
    np.testing.assert_array_equal(np.asarray(y),
                                  exact_ref(a, b, proto.field.p))


def test_small_window_field_guards_reference_and_pallas():
    """Mersenne-31's window (2) can't cover the single-fold eager paths:
    they must raise a descriptive error, never silently overflow."""
    f = Field(P_MERSENNE31)
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8, field=f)
    a = np.zeros((8, 8), np.int64)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="acc_window"):
        proto.run(a, a, key, mode="reference")
    with pytest.raises(ValueError, match="acc_window"):
        proto.run(a, a, key, mode="pallas")


def test_run_rejects_unknown_mode():
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    a = np.zeros((8, 8), np.int64)
    with pytest.raises(ValueError, match="unknown mode"):
        proto.run(a, a, jax.random.PRNGKey(0), mode="fusedd")


def test_fused_run_with_survivors_stays_on_staged_path():
    """A non-default mask runs the SAME compiled phase-1/2 program and the
    shared decode stage with cached survivor rows (DESIGN.md §5) — the
    pre-refactor fallback to ``run_reference`` is gone (the no-fallback
    guarantee itself is pinned in tests/test_elastic_engine.py)."""
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    rng = np.random.default_rng(0)
    a = rng.integers(0, proto.field.p, (8, 8))
    b = rng.integers(0, proto.field.p, (8, 8))
    surv = np.ones(proto.n_workers, bool)
    surv[:3] = False
    y = proto.run(a, b, jax.random.PRNGKey(1), survivors=surv)
    np.testing.assert_array_equal(np.asarray(y),
                                  exact_ref(a, b, proto.field.p))
    # the survivor set's decode table landed in the plan's LRU ...
    idx = tuple(int(i) for i in proto._survivor_prefix(surv))
    assert ("survivor", idx) in proto.plan._solve_cache
    # ... and the staged programs are attached to the plan, shared by twins
    assert "stages" in proto.plan._runners


# ----------------------------------------------------------------- planner


def test_plan_cache_hit_miss_semantics():
    cache_clear()
    base = cache_info()
    assert base == {"hits": 0, "misses": 0, "size": 0}
    p1 = get_plan("age", 2, 2, 2, None, DEFAULT_FIELD, 8)
    info = cache_info()
    assert info["misses"] == 1 and info["hits"] == 0 and info["size"] == 1
    p2 = get_plan("age", 2, 2, 2, None, DEFAULT_FIELD, 8)
    assert p2 is p1                       # the same object, not a rebuild
    info = cache_info()
    assert info["hits"] == 1 and info["misses"] == 1
    p3 = get_plan("age", 2, 2, 2, None, DEFAULT_FIELD, 16)  # m in the key
    assert p3 is not p1
    assert cache_info()["size"] == 2
    p4 = get_plan("age", 2, 2, 2, 1, DEFAULT_FIELD, 8)      # lam in the key
    assert p4 is not p1
    cache_clear()
    assert cache_info() == {"hits": 0, "misses": 0, "size": 0}


def test_protocol_instances_share_plan_and_compiled_runner():
    cache_clear()
    pa = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    pb = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    assert pa.plan is pb.plan
    rng = np.random.default_rng(0)
    a = rng.integers(0, pa.field.p, (8, 8))
    b = rng.integers(0, pa.field.p, (8, 8))
    pa.run(a, b, jax.random.PRNGKey(0))
    assert "stages" in pa.plan._runners   # staged programs built once ...
    stages = pa.plan._runners["stages"]
    pb.run(a, b, jax.random.PRNGKey(1))
    assert pb.plan._runners["stages"] is stages  # ... reused by the twin
    assert pb.plan.stages() is stages


def test_plan_key_distinguishes_field_prime():
    cache_clear()
    p1 = get_plan("age", 2, 2, 2, None, DEFAULT_FIELD, 8)
    p2 = get_plan("age", 2, 2, 2, None, Field(P_MERSENNE31), 8)
    assert p1 is not p2
    assert p1.p != p2.p
