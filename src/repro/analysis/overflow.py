"""Overflow verifier: interval proofs over the field pipeline (DESIGN.md §12).

Walks the exact dataflow of every integer-arithmetic stage the protocol
executes — the Barrett multiply-shift fold (:mod:`repro.kernels.barrett`),
the Pallas chunk-then-fold GEMM accumulator (:mod:`repro.kernels.
modmatmul`), the single-window polyeval (:mod:`repro.kernels.polyeval`),
the Karatsuba limb GEMM (:func:`repro.kernels.barrett.matmul_limbs`), the
served int8 limb GEMM (:func:`repro.kernels.barrett.field_matmul`), the
Montgomery REDC tables (:mod:`repro.mpc.montgomery`) and the decode/
assemble partial-sum refolds — in the interval domain of
:mod:`repro.analysis.intervals`, and proves no intermediate can leave its
container (int32 / int64 / uint64 / exact-f64).  :func:`verify_spec_space` then
quantifies the proof over every ``(scheme, s, t, λ, m, bk)`` the autotuner
can emit for a prime, so the ``acc_window`` contract is machine-checked
for the whole reachable configuration space, not just the shapes tests
happened to run.

:func:`certified_bk` derives the maximum provable accumulation window
*independently* (interval bisection — it never reads
:func:`repro.mpc.field.acc_window`), which is what makes the cross-check
``certified_bk(p) == acc_window(p)`` a proof rather than a tautology; the
kernels consume the certified value (:func:`repro.kernels.modmatmul.
_pick_blocks`).  :func:`certified_limb_k` certifies the int32 window of
the int8 limb GEMM the same way, and that GEMM chunks K by it.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, Optional

from ..mpc.field import P_DEFAULT, P_MERSENNE31
from .intervals import INT64_MAX, Interval

#: worker-budget ceiling used when quantifying over the tuner's space —
#: far above any closed-form N at the partition bound (s = t = 8, z = 8
#: needs ~1M? no: ~1k), so no feasible family member is clipped away
SPEC_SPACE_BUDGET = 4096

#: the kernels' VMEM-sized default K block (``_pick_blocks``)
DEFAULT_BK = 512


class OverflowProofError(AssertionError):
    """An interval proof obligation failed (a real overflow is reachable)."""


def _require(ok: bool, what: str, iv: Interval) -> None:
    if not ok:
        raise OverflowProofError(f"{what}: reachable range {iv!r}")


# ------------------------------------------------------------ certified bk
@functools.lru_cache(maxsize=None)
def certified_bk(p: int) -> int:
    """Largest ``bk`` provably safe for the chunk-then-fold accumulator.

    Proof obligation: a modular accumulator entry (``< p``) plus ``bk``
    raw products of residues stays inside int64.  Derived by interval
    bisection — NOT by calling :func:`repro.mpc.field.acc_window` — so
    the analyzer's self-check against the hand-derived window is an
    independent confirmation.  ``certified_bk(P_DEFAULT) == 2048``.
    """
    if p < 2:
        raise ValueError(f"need a modulus >= 2, got {p}")
    acc = Interval.residue(p)
    prod = Interval.residue(p) * Interval.residue(p)
    return _largest_safe(lambda q: (acc + prod.sum_n(q)).fits_int64)


@functools.lru_cache(maxsize=None)
def certified_limb_k(p: int) -> int:
    """Largest K chunk provably safe for the int8 limb GEMM's int32 sums.

    Proof obligation of :func:`repro.kernels.barrett.field_matmul`: one
    diagonal sum ``S_d = Σ_i A_i·B_{d−i}`` adds at most ``n_limbs(p)``
    limb-pair products per K step, each limb in ``[0, 2⁷−1]``, inside one
    int32 accumulator.  Derived by interval bisection; the GEMM chunks K
    by this value.  ``33286`` for ``2²⁶ − 5``, ``26628`` for Mersenne-31.
    """
    from ..kernels.barrett import LIMB_BITS, n_limbs

    limb = Interval(0, (1 << LIMB_BITS) - 1)
    group = (limb * limb).sum_n(n_limbs(p))
    return _largest_safe(lambda k: group.sum_n(k).fits_int32)


def _largest_safe(safe) -> int:
    """Largest ``q ≥ 1`` with ``safe(q)`` for a monotone predicate (1 if
    none): doubling, then bisection."""
    if not safe(1):
        return 1
    lo, hi = 1, 2
    while safe(hi):
        lo, hi = hi, hi * 2
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if safe(mid) else (lo, mid)
    return lo


# ------------------------------------------------------------ stage proofs
def prove_barrett_fold(p: int) -> None:
    """The pseudo-Mersenne fold reduces any ``x < 2⁶³`` to ``[0, p)``.

    Replays :func:`repro.kernels.barrett.mod_p`'s unrolled fold over the
    full input domain: every ``c·(x>>b) + (x & mask)`` intermediate must
    fit int64, the declared ``n_folds`` must actually reach ``< 2p``, and
    the final conditional subtract must land in ``[0, p)``.
    """
    from ..kernels.barrett import barrett_params

    params = barrett_params(p)
    if params is None:
        return          # non-pseudo-Mersenne: mod_p falls back to `%`
    b, c, n_folds = params
    x = Interval.nonneg_below(1 << 63)
    for _ in range(n_folds):
        hi_term = x.rshift(b).scale(c)
        _require(hi_term.fits_int64, f"Barrett c*(x>>b) overflows (p={p})",
                 hi_term)
        x = hi_term + x.mask_low(b)
        _require(x.fits_int64, f"Barrett fold sum overflows (p={p})", x)
    _require(x.hi < 2 * p,
             f"Barrett fold does not converge below 2p in {n_folds} folds "
             f"(p={p})", x)
    reduced = Interval(0, min(x.hi, p - 1)).union(
        Interval(0, x.hi - p) if x.hi >= p else Interval(0, 0))
    _require(reduced.within(0, p - 1),
             f"Barrett conditional subtract leaves [0, p) (p={p})", reduced)


def prove_acc_chain(p: int, bk: int, n_chunks: int = 1) -> None:
    """The kernel accumulator at K-block ``bk`` (+ the n-chunk refold).

    One output tile holds a residue (``< p``, from the previous fold) and
    accumulates ``bk`` raw products before the next fold — the exact
    schedule of ``_modmatmul_kernel`` — so ``acc + bk·(p−1)²`` must fit
    int64 (which is also :func:`repro.kernels.barrett.mod_p`'s domain).
    The jnp path (:func:`repro.kernels.barrett.matmul_folded`) additionally
    sums ``n_chunks`` folded partials before a last fold.
    """
    if bk < 1:
        raise ValueError(f"need bk >= 1, got {bk}")
    acc = Interval.residue(p)
    prod = Interval.residue(p) * Interval.residue(p)
    chain = acc + prod.sum_n(bk)
    _require(chain.fits_int64,
             f"accumulator overflows int64 at bk={bk} (p={p}, certified "
             f"max {certified_bk(p)})", chain)
    refold = Interval.residue(p).sum_n(max(1, n_chunks))
    _require(refold.fits_int64,
             f"chunk refold overflows int64 at n_chunks={n_chunks} (p={p})",
             refold)


def prove_polyeval(p: int, k_terms: int) -> None:
    """The single-window polyeval kernel: K raw MACs, then one fold."""
    if k_terms < 1:
        raise ValueError(f"need k_terms >= 1, got {k_terms}")
    prod = Interval.residue(p) * Interval.residue(p)
    acc = prod.sum_n(k_terms)
    _require(acc.fits_int64,
             f"polyeval K={k_terms} exceeds one accumulation window "
             f"(p={p}, certified {certified_bk(p)})", acc)


def prove_limb_gemm(p: int, k: int) -> None:
    """The Karatsuba limb GEMM's f64 partials are mantissa-exact.

    Mirrors :func:`repro.kernels.barrett.matmul_limbs`: ``lb``-bit limbs,
    three f64 matmuls whose partial sums must stay ≤ 2⁵³, then the int64
    recombination ``hh·s2 + mid·s1`` (+ folded ``ll``) under ``mod_p``'s
    domain.
    """
    if p.bit_length() > 31:
        raise OverflowProofError(
            f"limb recombination needs p < 2^31, got {p}")
    if k < 1:
        raise ValueError(f"need k >= 1, got {k}")
    lb = (p.bit_length() + 1) // 2
    hi_limb = Interval(0, (p - 1) >> lb)
    lo_limb = Interval(0, min(p - 1, (1 << lb) - 1))
    hh = (hi_limb * hi_limb).sum_n(k)
    ll = (lo_limb * lo_limb).sum_n(k)
    mid_sum = ((hi_limb + lo_limb) * (hi_limb + lo_limb)).sum_n(k)
    for name, iv in (("hh", hh), ("ll", ll), ("(ah+al)(bh+bl)", mid_sum)):
        _require(iv.fits_f64_mantissa,
                 f"limb GEMM partial {name} exceeds the f64 mantissa at "
                 f"K={k} (p={p})", iv)
    # the true middle term Σ ah·bl + al·bh is what reaches int64 + mod_p
    mid_true = (hi_limb * lo_limb + lo_limb * hi_limb).sum_n(k)
    _require(mid_true.fits_int64 and mid_true.lo >= 0,
             f"limb GEMM middle term leaves mod_p's domain at K={k} "
             f"(p={p})", mid_true)
    recomb = (Interval.residue(p) * Interval.residue(p)
              + Interval.residue(p) * Interval.residue(p))
    _require(recomb.fits_int64,
             f"limb recombination hh*s2 + mid*s1 overflows int64 (p={p})",
             recomb)
    final = Interval.residue(p) + Interval.residue(p)
    _require(final.fits_int64, "limb final fold leaves int64", final)


def prove_int8_limb_gemm(p: int, k: int) -> None:
    """The served field GEMM at inner dim ``k`` stays in int32 / int64.

    Mirrors :func:`repro.kernels.barrett.field_matmul`: K cut into
    ``chunks`` equal chunks of at most :func:`certified_limb_k`; per chunk
    the diagonal sums fit int32; the chunk sums fit int64 and fold to
    residues; the :func:`~repro.kernels.barrett.limb_schedule`
    recombination never leaves int64 and ends in ``mod_p``'s domain.
    """
    from ..kernels.barrett import (
        INT32_MAX, LIMB_BITS, limb_schedule, n_limbs)

    if p.bit_length() > 31:
        raise OverflowProofError(
            f"limb recombination needs p < 2^31, got {p}")
    if k < 1:
        raise ValueError(f"need k >= 1, got {k}")
    chunks = -(-k // certified_limb_k(p))
    kc = -(-k // chunks)
    limb = Interval(0, (1 << LIMB_BITS) - 1)
    diag = (limb * limb).sum_n(n_limbs(p) * kc)
    _require(diag.fits_int32,
             f"limb GEMM diagonal sum leaves int32 at K-chunk {kc} (p={p})",
             diag)
    _require(diag.sum_n(chunks).fits_int64,
             f"limb GEMM chunk sum leaves int64 at {chunks} chunks (p={p})",
             diag.sum_n(chunks))
    s = Interval(0, INT32_MAX if chunks == 1 else p - 1)
    total = Interval.const(0)
    for w, fold in limb_schedule(p, s.hi):
        if fold:
            total = Interval.residue(p)
        total = total + s.scale(w)
        _require(total.fits_int64,
                 f"limb recombination leaves int64 (p={p}, K={k})", total)


def prove_montgomery(p: int) -> None:
    """REDC never wraps uint64 and its output fits one subtract.

    Mirrors :class:`repro.mpc.montgomery.MontgomeryCtx`: ``T = a·b`` of
    residues (or ``a·R² mod p`` entering the domain), ``m < R``, and
    ``T + m·p`` must fit uint64; the shifted result must be ``< 2p``.
    """
    r = 1 << 32
    if p % 2 == 0 or not (2 < p < 2**31):
        raise OverflowProofError(f"Montgomery context needs odd p < 2^31, "
                                 f"got {p}")
    t = Interval.residue(p) * Interval.residue(p)
    m = Interval(0, r - 1)
    lifted = t + m.scale(p)
    _require(lifted.fits_uint64,
             f"REDC T + m*p wraps uint64 (p={p})", lifted)
    out = lifted.rshift(32)
    _require(out.hi < 2 * p,
             f"REDC output needs more than one conditional subtract "
             f"(p={p})", out)


def prove_assemble(p: int, max_terms: int = 1 << 20) -> None:
    """Decode/assemble partial-sum refolds stay in int64.

    Covers :func:`repro.mpc.tiling.assemble` (``gk`` folded partials per
    output tile) and the survivor-decode row mixes: ``max_terms`` residues
    summed raw.  ``2²⁰`` terms is far above any tile/row count a ≤ 2⁶³
    workload can produce yet still proves ~2⁴³ of slack for both primes.
    """
    total = Interval.residue(p).sum_n(max_terms)
    _require(total.fits_int64,
             f"assemble refold of {max_terms} residues overflows int64 "
             f"(p={p})", total)


# ------------------------------------------------------- pipeline + space
def verify_field_pipeline(p: int, *, bk: Optional[int] = None,
                          k_gemm: int = 256, k_poly: Optional[int] = None,
                          n_chunks: int = 64) -> Dict[str, int]:
    """Prove every stage of the field pipeline for one prime.

    ``bk`` defaults to the kernels' effective block (``min(512,
    certified_bk(p))``); passing a wider one is how the mutation test
    demonstrates rejection.  Returns the certified parameters.
    """
    cert = certified_bk(p)
    eff_bk = min(DEFAULT_BK, cert) if bk is None else bk
    prove_barrett_fold(p)
    prove_acc_chain(p, eff_bk, n_chunks)
    prove_polyeval(p, k_poly if k_poly is not None else min(cert, 128))
    prove_limb_gemm(p, min(k_gemm, 1 << (53 - 2 * ((p.bit_length() + 1)
                                                   // 2) - 2)))
    prove_int8_limb_gemm(p, k_gemm)
    prove_int8_limb_gemm(p, certified_limb_k(p) + 1)   # the chunked path
    prove_montgomery(p)
    prove_assemble(p)
    return {"p": p, "certified_bk": cert, "verified_bk": eff_bk}


def _tuner_space(z_range: Iterable[int], a_range: Iterable[int],
                 budget: int):
    """Every ``(scheme, s, t, λ, N, z, a)`` the tuner can emit."""
    from ..mpc.autotune import MAX_PARTITION, _feasible

    schemes = ("age", "entangled", "polydot")
    axis = range(1, MAX_PARTITION + 1)
    for z in z_range:
        for a in a_range:
            for scheme, s, t, lam, n in _feasible(
                    budget, z, schemes, axis, axis, None, a):
                yield scheme, s, t, lam, n, z, a


def verify_spec_space(p: int, *, max_m: int = 256,
                      z_range: Optional[Iterable[int]] = None,
                      a_range: Iterable[int] = (0, 1, 2),
                      budget: int = SPEC_SPACE_BUDGET) -> Dict[str, int]:
    """Quantify the pipeline proof over the tuner-reachable space.

    For every family member :func:`repro.mpc.autotune._feasible` yields
    (all schemes, both partition axes to ``MAX_PARTITION``, every gap,
    every ``z`` in ``z_range``, every adversary budget in ``a_range``)
    and every block side ``m ≤ max_m`` with ``s|m`` and ``t|m`` (a
    superset of both the tuner's ``lcm·2ʲ`` family and ``retune_spec``'s
    divisor walk), prove:

    * phase-1 shares / MAC tags:   polyeval at ``K = ts+z``,
    * phase-3 decode:              polyeval at ``K = t²+z+2a``,
    * exchange mix:                polyeval at ``K = N``,
    * phase-2 worker GEMM:         the ``bk = min(512, certified, m/s)``
      accumulator chain (plus the jnp refold at its chunk count),
    * the served int8 limb GEMM at every one of those inner dims,

    routing any K beyond one window through the chunked-path obligation
    exactly as the kernels do.  Returns counting stats; raises
    :class:`OverflowProofError` on the first unprovable config.
    """
    z_range = range(1, 9) if z_range is None else z_range
    cert = certified_bk(p)
    window_checks: set = set()      # distinct (kind, K/bk, chunks) proofs
    configs = 0
    max_k_seen = 0
    for scheme, s, t, lam, n, z, a in _tuner_space(z_range, a_range,
                                                   budget):
        configs += 1
        for k_terms in (t * s + z, t * t + z + 2 * a, n):
            max_k_seen = max(max_k_seen, k_terms)
            window_checks.add(("int8", k_terms, 0))
            if k_terms <= cert:
                window_checks.add(("poly", k_terms, 1))
            else:       # kernels refuse; the chunked path serves this K
                bk = min(DEFAULT_BK, cert)
                window_checks.add(("chain", bk, -(-k_terms // bk)))
        step = s * t // math.gcd(s, t)
        lcm = step
        while lcm <= max_m:
            k_inner = lcm // s
            if k_inner >= 1:
                bk = max(1, min(DEFAULT_BK, cert, k_inner))
                window_checks.add(("chain", bk, -(-k_inner // bk)))
                window_checks.add(("int8", k_inner, 0))
            lcm += step
    prove_barrett_fold(p)
    prove_montgomery(p)
    prove_assemble(p)
    for kind, kk, chunks in sorted(window_checks):
        if kind == "poly":
            prove_polyeval(p, kk)
        elif kind == "chain":
            prove_acc_chain(p, kk, chunks)
        else:
            prove_int8_limb_gemm(p, kk)
    return {"p": p, "configs": configs, "distinct_proofs":
            len(window_checks), "certified_bk": cert,
            "max_inner_dim": max_k_seen}


def self_check() -> Dict[int, int]:
    """The analyzer's own consistency gate: the independently derived
    window must equal the hand-derived :func:`repro.mpc.field.acc_window`
    on both shipped primes, and one-past-the-window must be rejected."""
    from ..mpc.field import acc_window

    out = {}
    for p in (P_DEFAULT, P_MERSENNE31):
        cert = certified_bk(p)
        hand = acc_window(p)
        if cert != hand:
            raise OverflowProofError(
                f"certified_bk({p})={cert} != acc_window={hand}: the "
                f"interval proof and the hand derivation disagree")
        over = Interval.residue(p) + (Interval.residue(p)
                                      * Interval.residue(p)).sum_n(cert + 1)
        if over.fits_int64:
            raise OverflowProofError(
                f"bk={cert + 1} unexpectedly fits int64 for p={p}: the "
                f"window is not maximal (hi={over.hi} <= {INT64_MAX})")
        out[p] = cert
    return out
