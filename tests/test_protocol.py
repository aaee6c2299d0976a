"""End-to-end protocol tests: all 3 phases, stragglers, baselines, privacy."""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dep: deterministic fallback sweeps
    from _hypothesis_compat import given, settings
    from _hypothesis_compat import strategies as st

from repro.mpc import AGECMPCProtocol
from repro.mpc.elastic import ElasticPool
from repro.mpc.field import Field, P_DEFAULT


def exact_ref(a, b, p):
    return np.array((a.astype(object).T @ b.astype(object)) % p, dtype=np.int64)


@pytest.mark.parametrize(
    "s,t,z,m",
    [(2, 2, 2, 8), (1, 2, 1, 8), (2, 1, 2, 8), (3, 2, 2, 12),
     (2, 3, 3, 12), (1, 3, 2, 9), (4, 2, 1, 8)],
)
def test_roundtrip_exact(s, t, z, m):
    proto = AGECMPCProtocol(s=s, t=t, z=z, m=m)
    rng = np.random.default_rng(42)
    a = rng.integers(0, proto.field.p, (m, m))
    b = rng.integers(0, proto.field.p, (m, m))
    y = proto.run(a, b, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(y), exact_ref(a, b, proto.field.p))


@pytest.mark.parametrize("scheme", ["age", "entangled", "polydot"])
def test_baseline_schemes_execute(scheme):
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8, scheme=scheme)
    rng = np.random.default_rng(1)
    a = rng.integers(0, proto.field.p, (8, 8))
    b = rng.integers(0, proto.field.p, (8, 8))
    y = proto.run(a, b, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(y), exact_ref(a, b, proto.field.p))


def test_scheme_worker_ordering():
    """Executable N's respect the paper's dominance (Lemmas 4 & 7)."""
    age = AGECMPCProtocol(s=2, t=2, z=2, m=8, scheme="age")
    ent = AGECMPCProtocol(s=2, t=2, z=2, m=8, scheme="entangled")
    pd = AGECMPCProtocol(s=2, t=2, z=2, m=8, scheme="polydot")
    assert age.n_workers <= ent.n_workers
    assert age.n_workers <= pd.n_workers


def test_straggler_tolerance_any_subset():
    """Decode succeeds from ANY t²+z surviving workers (coded FT)."""
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    rng = np.random.default_rng(7)
    a = rng.integers(0, proto.field.p, (8, 8))
    b = rng.integers(0, proto.field.p, (8, 8))
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    f_a, f_b = proto.phase1_shares(a, b, k1)
    h = proto.phase2_compute(f_a, f_b)
    i_pts = proto.phase2_exchange(h, k2)
    want = exact_ref(a, b, proto.field.p)
    thr = proto.recovery_threshold
    for seed in range(5):
        surv = np.zeros(proto.n_workers, bool)
        keep = np.random.default_rng(seed).choice(
            proto.n_workers, thr, replace=False)
        surv[keep] = True
        y = proto.decode(i_pts, surv)
        np.testing.assert_array_equal(np.asarray(y), want)


def test_below_threshold_raises():
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    surv = np.zeros(proto.n_workers, bool)
    surv[: proto.recovery_threshold - 1] = True
    with pytest.raises(RuntimeError, match="threshold"):
        proto.decode(np.zeros((proto.n_workers, 4, 4), np.int64), surv)


def test_fixed_point_float_path():
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=8)
    f = proto.field
    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 8))
    b = rng.standard_normal((8, 8))
    y = proto.run(f.encode(a), f.encode(b), jax.random.PRNGKey(0))
    dec = np.asarray(f.decode(y, products=2))
    np.testing.assert_allclose(dec, a.T @ b, atol=0.05)


def test_privacy_masking_is_perfect():
    """A single worker's share of A is a deterministic function of the mask:
    choosing masks uniformly makes shares of any two inputs identically
    distributed.  We verify the stronger structural condition (invertible
    secret-power Vandermonde for colluding subsets) + a direct example:
    shares of A and A' coincide under a compensating mask shift."""
    proto = AGECMPCProtocol(s=2, t=2, z=2, m=4)
    proto.check_privacy_structure(n_subsets=64)

    f = proto.field
    rng = np.random.default_rng(5)
    a1 = rng.integers(0, f.p, (4, 4))
    a2 = rng.integers(0, f.p, (4, 4))
    # worker n sees F_A(α_n) = Σ coded + Σ secret·α^pw. For ANY z-subset the
    # secret Vandermonde is invertible => exists mask' with
    # C_{A1}(α)+S(α) == C_{A2}(α)+S'(α) for that subset. Check for z workers.
    from repro.mpc.lagrange import inv_mod, vandermonde
    sub = [0, 1]  # any z=2 workers
    ca = np.asarray(proto.vand_a)[:, : proto.s * proto.t]
    sa = np.asarray(proto.vand_a)[:, proto.s * proto.t:]
    blocks1 = np.asarray(proto._split_a(a1)).reshape(proto.s * proto.t, -1)
    blocks2 = np.asarray(proto._split_a(a2)).reshape(proto.s * proto.t, -1)
    delta = (ca[sub].astype(object) @ (blocks1 - blocks2).astype(object)) % f.p
    v = sa[sub]
    shift = (inv_mod(f, v).astype(object) @ delta) % f.p  # mask correction
    # share(A1, mask=0) == share(A2, mask=shift) on the colluding subset
    lhs = (ca[sub].astype(object) @ blocks1.astype(object)) % f.p
    rhs = (ca[sub].astype(object) @ blocks2.astype(object)
           + v.astype(object) @ shift) % f.p
    assert np.array_equal(lhs, rhs)


def test_elastic_pool_and_replan():
    pool = ElasticPool(s=2, t=2, z=2, m=8, spares=3)
    assert pool.pool_size == pool.proto.n_workers + 3
    pool.fail([0, 5, 17])
    idx, w = pool.reconstruction_weights()
    assert len(idx) == pool.proto.n_workers
    assert 0 not in idx and 5 not in idx
    # drive below N -> replan to feasible (s', t')
    pool.fail(list(range(6, 15)))
    with pytest.raises(RuntimeError):
        pool.active_subset()
    new = pool.replan()
    assert new is not None
    assert new.n_workers <= pool.alive.sum()


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([1, 2, 3]),
    t=st.sampled_from([1, 2, 3]),
    z=st.integers(1, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_protocol_roundtrip(s, t, z, seed):
    """Property: decode(run(A,B)) == AᵀB mod p for random shapes/inputs."""
    if s == 1 and t == 1:
        s = 2
    m = 6 * max(s, t) if (6 % s or 6 % t) else 6
    m = s * t * 2  # divisible by both
    proto = AGECMPCProtocol(s=s, t=t, z=z, m=m)
    rng = np.random.default_rng(seed)
    a = rng.integers(0, proto.field.p, (m, m))
    b = rng.integers(0, proto.field.p, (m, m))
    y = proto.run(a, b, jax.random.PRNGKey(seed % 2**31))
    np.testing.assert_array_equal(np.asarray(y), exact_ref(a, b, proto.field.p))


def test_field_matmul_windows():
    """The field GEMM is exact vs the object-dtype reference on both sides
    of its certified K-chunk window."""
    from repro.analysis.overflow import certified_limb_k

    f = Field(P_DEFAULT)
    rng = np.random.default_rng(0)
    k_max = certified_limb_k(f.p)
    for k in (1, 4, 300, k_max, k_max + 1):
        a = rng.integers(0, f.p, (3, k))
        b = rng.integers(0, f.p, (k, 2))
        want = np.array((a.astype(object) @ b.astype(object)) % f.p,
                        np.int64)
        np.testing.assert_array_equal(np.asarray(f.matmul(a, b)), want)
