"""The static-analysis subsystem (DESIGN.md §12): overflow certificates,
jit-stability lint, invariant prover, and the baseline/suppression gate.

The load-bearing claims:

* the interval verifier's independently-derived ``certified_bk`` agrees
  with the runtime closed form ``acc_window`` on both shipped primes —
  and the *kernel itself* is bit-exact against the reference at exactly
  that certified corner (analyzer-vs-runtime agreement);
* a mutated, over-wide block is *rejected* — by the prover
  (``OverflowProofError``) and by the kernel (``ValueError``) alike;
* each lint rule fires on its minimal trigger, honors inline
  ``# analysis: allow``, and the fingerprint baseline absorbs audited
  sites but resurrects them when the line is edited.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis import intervals, invariants, jitlint, overflow
from repro.analysis.report import (Finding, diff_baseline, load_baseline,
                                   write_baseline)
from repro.kernels.modmatmul import modmatmul
from repro.kernels.ref import modmatmul_ref
from repro.mpc.field import ACC_WINDOW, P_DEFAULT, P_MERSENNE31, acc_window

PRIMES = (P_DEFAULT, P_MERSENNE31)


# ------------------------------------------------------- overflow verifier
def test_certified_bk_matches_acc_window():
    """The interval derivation and the closed form agree on both primes."""
    assert overflow.self_check() == {P_DEFAULT: 2048, P_MERSENNE31: 2}
    for p in PRIMES:
        assert overflow.certified_bk(p) == acc_window(p) == ACC_WINDOW[p]


@pytest.mark.parametrize("p", PRIMES)
def test_field_pipeline_certifies(p):
    stats = overflow.verify_field_pipeline(p)
    assert stats["certified_bk"] == acc_window(p)
    assert stats["verified_bk"] == min(512, acc_window(p))


@pytest.mark.parametrize("p", PRIMES)
def test_mutated_overwide_bk_rejected(p):
    """Widening the block past the window must fail the proof."""
    cert = overflow.certified_bk(p)
    with pytest.raises(overflow.OverflowProofError):
        overflow.prove_acc_chain(p, cert + 1)
    with pytest.raises(overflow.OverflowProofError):
        overflow.verify_field_pipeline(p, bk=cert + 1)
    # the proof at the certified edge itself must hold
    overflow.prove_acc_chain(p, cert)


@pytest.mark.parametrize("p", PRIMES)
def test_certified_limb_k_is_the_int32_window(p):
    """The limb GEMM's K chunk is the largest with n·K·127² in int32."""
    from repro.kernels.barrett import n_limbs

    n, k = n_limbs(p), overflow.certified_limb_k(p)
    assert n * k * 127 ** 2 <= 2**31 - 1 < n * (k + 1) * 127 ** 2
    assert (n, k) == {P_DEFAULT: (4, 33286), P_MERSENNE31: (5, 26628)}[p]


@pytest.mark.parametrize("p", PRIMES)
def test_int8_limb_gemm_proves_at_every_chunk_count(p):
    cert = overflow.certified_limb_k(p)
    for k in (1, 300, cert, cert + 1, 3 * cert + 7, 1 << 20):
        overflow.prove_int8_limb_gemm(p, k)
    with pytest.raises(overflow.OverflowProofError):
        overflow.prove_int8_limb_gemm(2**31 + 11, 4)   # a 32-bit prime


@pytest.mark.parametrize("p", PRIMES)
def test_kernel_bit_exact_at_certified_corner(p):
    """Analyzer-vs-runtime agreement: all-(p−1) operands at the certified
    block are bit-exact against the reference — the exact corner the
    interval proof certifies (acc + bk·(p−1)² at the int64 edge)."""
    window = overflow.certified_bk(p)
    bk = min(512, window)
    k = 2 * bk                       # two chunks: exercises the refold too
    a = np.full((8, k), p - 1, np.int64)
    b = np.full((k, 8), p - 1, np.int64)
    got = np.asarray(modmatmul(a, b, p=p, bk=bk))
    want = np.asarray(modmatmul_ref(a, b, p=p))
    np.testing.assert_array_equal(got, want)
    # cross-check one entry against exact bignum arithmetic
    assert got[0, 0] == (k * (p - 1) * (p - 1)) % p


def test_kernel_rejects_overwide_bk():
    """The kernel consumes the certificate: bk past the window raises."""
    a = np.ones((4, 4), np.int64)
    with pytest.raises(ValueError, match="acc_window"):
        modmatmul(a, a, p=P_DEFAULT, bk=overflow.certified_bk(P_DEFAULT) + 1)
    with pytest.raises(ValueError, match="acc_window"):
        modmatmul(a, a, p=P_MERSENNE31, bk=3)


def test_spec_space_smoke():
    """A reduced slice of the tuner space proves end to end."""
    stats = overflow.verify_spec_space(
        P_DEFAULT, max_m=32, z_range=(1, 2), a_range=(0, 1))
    assert stats["configs"] > 0
    assert stats["distinct_proofs"] > 0


def test_interval_arithmetic_edges():
    iv = intervals.Interval(0, 7)
    assert (iv + iv).hi == 14
    assert (iv * iv).hi == 49
    assert iv.sum_n(3).hi == 21
    edge = intervals.Interval(0, 2**63 - 1)
    assert edge.fits_int64
    assert not (edge + intervals.Interval(1, 1)).fits_int64


# ------------------------------------------------------------ jit lint
def _lint(tmp_path, source, rules=jitlint.RULES):
    f = tmp_path / "snippet.py"
    f.write_text(source)
    return jitlint.lint_file(str(f), rules)


def test_lint_host_sync(tmp_path):
    src = ("import numpy as np\n"
           "def f(x):\n"
           "    a = np.asarray(x)\n"
           "    b = x.item()\n"
           "    jax.block_until_ready(x)\n"
           "    return a, b\n")
    rules = [f.rule for f in _lint(tmp_path, src)]
    assert rules.count("host-sync") == 3


def test_lint_traced_branch(tmp_path):
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x, n):\n"
           "    if n > 3:\n"
           "        return x\n"
           "    return x + 1\n")
    found = _lint(tmp_path, src)
    assert any(f.rule == "traced-branch" for f in found)
    # static_argnames exempts the parameter
    src_ok = ("import jax\n"
              "from functools import partial\n"
              "@partial(jax.jit, static_argnames=('n',))\n"
              "def f(x, n):\n"
              "    if n > 3:\n"
              "        return x\n"
              "    return x + 1\n")
    assert not any(f.rule == "traced-branch"
                   for f in _lint(tmp_path, src_ok))


def test_lint_static_argnums(tmp_path):
    src = ("import jax\n"
           "g = jax.jit(lambda x, n: x, static_argnums=(1,))\n")
    assert any(f.rule == "static-argnums" for f in _lint(tmp_path, src))


def test_lint_shape_loop(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def f(n):\n"
           "    out = []\n"
           "    for i in range(n):\n"
           "        out.append(jnp.zeros((i, 4)))\n"
           "    return out\n")
    assert any(f.rule == "shape-loop" for f in _lint(tmp_path, src))


def test_lint_donated_reuse(tmp_path):
    src = ("import jax\n"
           "step = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
           "def train(state, batch):\n"
           "    out = step(state, batch)\n"  # state donated, not reassigned
           "    return state, out\n")
    assert any(f.rule == "donated-reuse" for f in _lint(tmp_path, src))
    src_ok = src.replace("out = step", "state = step").replace(
        "return state, out", "return state")
    assert not any(f.rule == "donated-reuse"
                   for f in _lint(tmp_path, src_ok))


def test_lint_bare_assert(tmp_path):
    assert any(f.rule == "no-bare-assert"
               for f in _lint(tmp_path, "def f(x):\n    assert x\n"))


def test_lint_suppression_same_line_and_above(tmp_path):
    same = ("import numpy as np\n"
            "def f(x):\n"
            "    return np.asarray(x)  # analysis: allow(host-sync)\n")
    above = ("import numpy as np\n"
             "def f(x):\n"
             "    # analysis: allow(host-sync): test fixture\n"
             "    return np.asarray(x)\n")
    star = ("import numpy as np\n"
            "def f(x):\n"
            "    return np.asarray(x)  # analysis: allow(*)\n")
    too_far = ("import numpy as np\n"
               "def f(x):\n"
               "    # analysis: allow(host-sync)\n"
               "    # an interposed comment breaks the suppression\n"
               "    return np.asarray(x)\n")
    assert _lint(tmp_path, same) == []
    assert _lint(tmp_path, above) == []
    assert _lint(tmp_path, star) == []
    assert any(f.rule == "host-sync" for f in _lint(tmp_path, too_far))


def test_no_bare_asserts_in_src():
    """Satellite acceptance: zero bare asserts anywhere under src/."""
    found = jitlint.lint_paths(["src"], rules=("no-bare-assert",))
    assert found == [], "\n".join(f.render() for f in found)


# ------------------------------------------------------------- baseline
def test_baseline_absorbs_then_resurrects(tmp_path):
    src_file = tmp_path / "legacy.py"
    src_file.write_text("import numpy as np\n"
                        "def f(x):\n"
                        "    return np.asarray(x)\n")
    found = jitlint.lint_file(str(src_file))
    assert len(found) == 1
    base = tmp_path / "baseline.json"
    write_baseline(str(base), found)
    loaded = load_baseline(str(base))
    assert sum(loaded.values()) == 1
    # absorbed: same line text → no fresh findings
    assert diff_baseline(jitlint.lint_file(str(src_file)), loaded) == []
    # editing the line invalidates the fingerprint → finding resurrects
    src_file.write_text("import numpy as np\n"
                        "def f(x):\n"
                        "    return np.asarray(x + 1)\n")
    fresh = diff_baseline(jitlint.lint_file(str(src_file)), loaded)
    assert len(fresh) == 1
    # duplicate sites beyond the audited count leak as new debt
    dup = Finding(rule="host-sync", file=str(src_file), line=3,
                  message="", snippet="return np.asarray(x)")
    assert len(diff_baseline([dup, dup], {dup.fingerprint(): 1})) == 1


def test_committed_baseline_is_current():
    """The checked-in baseline absorbs the tree's jitlint findings —
    exactly what the CI analyze job asserts (without re-running the
    expensive overflow/invariant passes)."""
    loaded = load_baseline("analysis-baseline.json")
    assert loaded, "analysis-baseline.json missing or empty"
    fresh = diff_baseline(jitlint.lint_paths(["src"]), loaded)
    assert fresh == [], "\n".join(f.render() for f in fresh)


def test_cli_gate(tmp_path):
    """`python -m repro.analysis` exits 0 on a clean file, 1 on a dirty
    one, and a written baseline flips dirty back to 0."""
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x + 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import numpy as np\n"
                     "def f(x):\n"
                     "    return np.asarray(x)\n")
    env_cmd = [sys.executable, "-m", "repro.analysis",
               "--passes", "jitlint"]
    r = subprocess.run(env_cmd + [str(clean)], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(env_cmd + [str(dirty)], capture_output=True,
                       text=True)
    assert r.returncode == 1 and "FAILED" in r.stdout
    base = tmp_path / "b.json"
    r = subprocess.run(env_cmd + [str(dirty), "--write-baseline",
                                  str(base)], capture_output=True,
                       text=True)
    assert r.returncode == 0 and json.loads(base.read_text())["total"] == 1
    r = subprocess.run(env_cmd + [str(dirty), "--baseline", str(base)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------------ invariants
def test_invariants_smoke():
    assert invariants.prove_spec_gate(z_range=(1, 2), a_range=(0, 1)) > 0
    assert invariants.prove_feasible_path(budget=64, z_range=(1, 2),
                                          a_range=(0, 1)) > 0
    assert invariants.audit_escalation_sources("src") == 2


def test_invariants_closed_forms():
    assert invariants.prove_closed_forms() > 0


def test_regime_classifier_spot_checks():
    """U-regime classification at hand-checked cells (Theorem 3)."""
    # λ=0: U1 iff z > ts−s
    assert invariants._regime(2, 2, 3, 0) == "U1"
    assert invariants._regime(2, 3, 3, 0) == "U2"
    # λ=z collapses to U3
    assert invariants._regime(2, 2, 3, 3) == "U3"
    assert invariants._regime(1, 2, 5, 5) == "U3"
