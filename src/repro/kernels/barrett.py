"""Barrett-style modular reduction for the field fast path (DESIGN.md §3).

Both supported primes are *pseudo-Mersenne*: ``p = 2^b − c`` with tiny ``c``
(``2²⁶ − 5`` and ``2³¹ − 1``).  For such primes the Barrett quotient step
``q = ⌊x·μ / 2^k⌋`` collapses to a multiply-shift *fold*::

    x ≡ c · (x >> b) + (x & (2^b − 1))   (mod p)

Each fold shrinks ``x`` by ~``b − log₂(c)`` bits; a statically-unrolled
handful of folds plus one conditional subtract reduces any non-negative
int64 (``x < 2⁶³``) to ``[0, p)`` with **no integer division** — the
operation XLA/Pallas lowers to shifts, masks and adds, all VPU-friendly.
The fold count is computed at trace time from the worst-case bound, so the
jitted program contains exactly the folds it needs and nothing else.

``mod_p`` is the shared reduction primitive used by

* the Pallas kernels (:mod:`repro.kernels.modmatmul`,
  :mod:`repro.kernels.polyeval`) for their per-K-block folds, and
* :func:`field_matmul`, the int8 limb GEMM behind every served stage
  (:mod:`repro.mpc.planner`, :mod:`repro.mpc.secure_matmul`).

:func:`matmul_limbs` (f64) and :func:`matmul_folded` (int64 dot) are off
the served path: XLA:TPU lowers no int64 dot and emulates f64.

For a prime that is *not* pseudo-Mersenne we fall back to the hardware
remainder (``%``) so the helpers stay total.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_MAX_INPUT_BITS = 63  # mod_p domain: 0 <= x < 2^63 (non-negative int64)


@functools.lru_cache(maxsize=None)
def barrett_params(p: int):
    """``(b, c, n_folds)`` for the pseudo-Mersenne fold, or ``None``.

    ``n_folds`` is the number of ``c·hi + lo`` folds after which the
    worst-case value is provably ``< 2p`` (so one conditional subtract
    finishes the reduction).  Returns ``None`` when the fold does not
    converge quickly (``c`` too large relative to ``2^b``).
    """
    if p < 3:
        return None
    b = p.bit_length()
    c = (1 << b) - p
    bound = (1 << _MAX_INPUT_BITS) - 1
    for n_folds in range(1, 8):
        bound = c * (bound >> b) + ((1 << b) - 1)
        if bound < 2 * p:
            return b, c, n_folds
    return None


def mod_p(x, p: int):
    """``x mod p`` for non-negative int64 ``x < 2⁶³`` via multiply-shift.

    Exact drop-in for ``x % p`` on the fast-path primes; traces to shifts,
    masks, adds and one ``where`` — no integer division.
    """
    params = barrett_params(p)
    if params is None:
        return x % p
    b, c, n_folds = params
    mask = (1 << b) - 1
    x = jnp.asarray(x)
    for _ in range(n_folds):
        x = c * (x >> b) + (x & mask)
    return jnp.where(x >= p, x - p, x)


def matmul_limbs(a, b, *, p: int):
    """Exact ``(a @ b) mod p`` through limb-decomposed f64 matmuls.

    XLA has no fast integer GEMM on CPU (int64 matmul lowers to scalar
    loops), but float64 GEMM is exact for integer values below 2⁵³.  Split
    each operand into two ``lb``-bit limbs (``lb = ⌈bits(p)/2⌉``) and form
    the product Karatsuba-style with THREE f64 matmuls::

        a·b = hh·2^{2lb} + (  (ah+al)(bh+bl) − hh − ll  )·2^{lb} + ll

    Every partial sum is an integer < 2^{2lb+2}·K ≤ 2⁵³, so the float
    pipeline is bit-exact; the limbs are then recombined in int64 with
    Barrett folds.  Not served: :func:`field_matmul` replaced it on every
    platform (DESIGN.md §3).  Requires ``K ≤ 2^{53−2lb−2}`` (2²⁵ for the
    default prime) — far above any protocol shape; larger K chunks
    recursively.  Leading batch dims broadcast like :func:`jnp.matmul`.
    """
    if p.bit_length() > 31:
        raise ValueError("limb recombination needs p < 2^31")
    lb = (p.bit_length() + 1) // 2
    k_max = 1 << (53 - (2 * lb + 2))
    a = jnp.asarray(a, jnp.int64)
    b = jnp.asarray(b, jnp.int64)
    k = a.shape[-1]
    if k > k_max:  # fold exact-size chunks (never hit by protocol shapes)
        out = None
        for lo in range(0, k, k_max):
            part = matmul_limbs(a[..., lo:lo + k_max],
                                b[..., lo:lo + k_max, :], p=p)
            out = part if out is None else mod_p(out + part, p)
        return out
    mask = (1 << lb) - 1
    ah = (a >> lb).astype(jnp.float64)
    al = (a & mask).astype(jnp.float64)
    bh = (b >> lb).astype(jnp.float64)
    bl = (b & mask).astype(jnp.float64)
    hh = jnp.matmul(ah, bh)
    ll = jnp.matmul(al, bl)
    mid = jnp.matmul(ah + al, bh + bl) - hh - ll
    hh = mod_p(hh.astype(jnp.int64), p)
    mid = mod_p(mid.astype(jnp.int64), p)
    s2 = (1 << (2 * lb)) % p
    s1 = (1 << lb) % p
    # hh·s2 + mid·s1 < 2·p² < 2⁶³; + (ll mod p) after one more fold
    return mod_p(mod_p(hh * s2 + mid * s1, p) + mod_p(ll.astype(jnp.int64), p), p)


#: limb width of :func:`field_matmul`: a 7-bit limb is a non-negative int8
LIMB_BITS = 7
INT32_MAX = 2**31 - 1
INT64_MAX = 2**63 - 1


def n_limbs(p: int) -> int:
    """7-bit limbs per residue: 4 for ``2²⁶ − 5``, 5 for Mersenne-31."""
    return max(1, -(-(p - 1).bit_length() // LIMB_BITS))


@functools.lru_cache(maxsize=None)
def limb_schedule(p: int, s_max: int):
    """Recombination schedule ``((w_d, fold_d), …)`` for ``d < 2·n_limbs−1``.

    ``Σ_d S_d · w_d`` with ``w_d = 2^{7d} mod p`` and every diagonal sum
    ``0 ≤ S_d ≤ s_max`` is accumulated in int64; ``fold_d`` marks where the
    running sum must be folded to ``[0, p)`` before adding term ``d`` so it
    can never leave int64.  Decided at trace time from the static bound, so
    the pseudo-Mersenne primes (small ``w_d``) get no intermediate fold.
    """
    sched, bound = [], 0
    for d in range(2 * n_limbs(p) - 1):
        w = pow(2, LIMB_BITS * d, p)
        fold = bound + s_max * w > INT64_MAX
        if fold:
            bound = p - 1
        bound += s_max * w
        sched.append((w, fold))
    return tuple(sched)


def _limbs(x, n: int):
    """``[n, *x.shape]`` int8 limbs, least significant first."""
    mask = (1 << LIMB_BITS) - 1
    return jnp.stack([((x >> (LIMB_BITS * i)) & mask).astype(jnp.int8)
                      for i in range(n)])


def _toeplitz(limbs):
    """``T[d, i] = X_{d−i}`` (zero outside ``[0, n)``): ``[2n−1, n, …]``."""
    n = limbs.shape[0]
    padded = jnp.pad(limbs, [(n - 1, n - 1)] + [(0, 0)] * (limbs.ndim - 1))
    idx = np.arange(2 * n - 1)[:, None] - np.arange(n)[None, :] + n - 1
    return padded[idx]


def field_matmul(a, b, *, p: int):
    """Exact ``(a @ b) mod p`` from int8×int8→int32 dots (DESIGN.md §3).

    The one field GEMM of the served path, on every platform.  ``a: [...,
    M, K]``, ``b: [..., K, N]`` residues in ``[0, p)``, ``p < 2³¹``;
    leading batch dims broadcast like :func:`jnp.matmul`.

    Each residue splits into ``n = n_limbs(p)`` 7-bit limbs, so
    ``a·b = Σ_d S_d·2^{7d}`` with diagonal sums ``S_d = Σ_i A_i·B_{d−i}``.
    The smaller operand's limbs are stacked Toeplitz-wise so that one
    ``dot_general`` with ``preferred_element_type=int32`` forms every
    ``S_d`` inside the accumulator (the MXU's exact integer GEMM on TPU; no
    int64 or f64 dot exists on that path).  K is cut into chunks of at most
    :func:`repro.analysis.overflow.certified_limb_k` — the interval
    certificate that ``n·K·127²`` fits int32 — and the ``S_d`` are
    recombined with int64 multiply-adds and :func:`mod_p` folds placed by
    :func:`limb_schedule`.  The ops sit under the named scope
    ``field_gemm``, split into ``field_gemm.split`` (limbs),
    ``field_gemm.dot`` (the int8 dot) and ``field_gemm.recombine``.
    """
    # lazy: repro.analysis.overflow imports this module (as _pick_blocks)
    from ..analysis.overflow import certified_limb_k

    if p.bit_length() > 31:
        raise ValueError(f"limb recombination needs p < 2^31, got {p}")
    with jax.named_scope("field_gemm"):
        with jax.named_scope("field_gemm.split"):
            # residues < 2³¹: split them in native 32-bit lanes, not
            # emulated int64
            a = jnp.asarray(a).astype(jnp.int32)
            b = jnp.asarray(b).astype(jnp.int32)
            k = a.shape[-1]
            chunks = max(1, -(-k // certified_limb_k(p)))
            kc = -(-k // chunks)
            pad = chunks * kc - k
            if pad:
                a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
                b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
            la = _limbs(a.reshape(*a.shape[:-1], chunks, kc), n_limbs(p))
            lb = _limbs(b.reshape(*b.shape[:-2], chunks, kc, b.shape[-1]),
                        n_limbs(p))
            small_a = a.size <= b.size
            if small_a:
                la = _toeplitz(la)
            else:
                lb = _toeplitz(lb)
        with jax.named_scope("field_gemm.dot"):
            s = jnp.einsum("di...mck,i...ckn->dc...mn" if small_a
                           else "i...mck,di...ckn->dc...mn", la, lb,
                           preferred_element_type=jnp.int32)
        with jax.named_scope("field_gemm.recombine"):
            s = s.astype(jnp.int64)             # [2n−1, chunks, ..., M, N]
            if chunks == 1:
                s, s_max = s[:, 0], INT32_MAX
            else:
                s, s_max = mod_p(jnp.sum(s, axis=1), p), p - 1
            out = s[0]                          # w_0 = 1, never a fold
            for d, (w, fold) in enumerate(limb_schedule(p, s_max)[1:], 1):
                out = (mod_p(out, p) if fold else out) + s[d] * w
            return mod_p(out, p)


def matmul_folded(a, b, *, p: int, window: int):
    """Exact ``(a @ b) mod p`` with chunk-then-fold accumulation + Barrett.

    ``a: [..., M, K]``, ``b: [..., K, N]`` int64 field elements (values in
    ``[0, p)``); leading batch dims broadcast like :func:`jnp.matmul`.
    ``window`` is the exact int64 accumulation window for ``p`` (see
    :func:`repro.mpc.field.acc_window`): up to ``window`` products are
    summed raw in int64, then folded with :func:`mod_p`: one XLA dot per
    K-chunk, one fold per chunk.  Not served (XLA:TPU refuses the int64
    dot); :func:`field_matmul` is the served GEMM.
    """
    a = jnp.asarray(a, jnp.int64)
    b = jnp.asarray(b, jnp.int64)
    k = a.shape[-1]
    if window <= 1 and k > 1:
        prods = mod_p(a[..., :, :, None] * b[..., None, :, :], p)
        return mod_p(jnp.sum(prods, axis=-2), p)
    if k <= window:
        return mod_p(jnp.matmul(a, b), p)
    n_chunks = -(-k // window)
    pad = n_chunks * window - k
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
    a = a.reshape(*a.shape[:-1], n_chunks, window)
    b = b.reshape(*b.shape[:-2], n_chunks, window, b.shape[-1])
    part = mod_p(jnp.einsum("...mcw,...cwn->...cmn", a, b), p)
    # n_chunks partial sums, each < p: the re-fold stays inside int64 for
    # any realistic K (n_chunks · p < 2⁶³ ⇔ K < window · 2⁶³/p).
    return mod_p(jnp.sum(part, axis=-3), p)
