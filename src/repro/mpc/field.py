"""Prime-field arithmetic for the executable CMPC protocols.

Default field: ``p = 2²⁶ − 5`` (prime).  Chosen so that products fit int64
with headroom for *chunked accumulation*: ``(p−1)² < 2⁵²``, so up to
``2¹¹ = 2048`` products can be summed in int64 before a modular fold.  This
"chunk-then-fold" window is the contract the Pallas kernel
(:mod:`repro.kernels.modmatmul`) is built around.

``p = 2³¹ − 1`` (Mersenne-31) is also supported for wider fixed-point
headroom.  Both primes multiply matrices through the 7-bit-limb int8 GEMM
(:func:`repro.kernels.barrett.field_matmul`, DESIGN.md §3).

All array ops are JAX (int64 via jax_enable_x64-free int32/int64 mixed mode:
we store field elements as int64 arrays; jax defaults allow int64 creation
only with x64 enabled, so we enable it at import for this subpackage).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels.barrett import field_matmul
from .errors import InvariantError

jax.config.update("jax_enable_x64", True)

P_DEFAULT = 2**26 - 5      # prime; (p-1)^2 * 2048 < 2^63
P_MERSENNE31 = 2**31 - 1   # prime; tiny window here; 8-bit limb path on TPU


def acc_window(p: int) -> int:
    """Exact int64 chunk-then-fold window for ``F_p`` (DESIGN.md §3).

    The largest ``q`` such that ``q·(p−1)² + (p−1) < 2⁶³``: a modular
    accumulator (``< p``) plus ``q`` raw products can never overflow int64.
    This is the SINGLE source of truth for the accumulation contract —
    ``ACC_WINDOW`` below, the Pallas kernels' ``bk`` cap
    (:mod:`repro.kernels.modmatmul`, :mod:`repro.kernels.polyeval`) and the
    fused protocol path all derive from it.
    """
    return max(1, (2**63 - p) // ((p - 1) ** 2))


# max #products accumulable in int64 before a fold, per field (derived)
ACC_WINDOW = {P_DEFAULT: acc_window(P_DEFAULT),
              P_MERSENNE31: acc_window(P_MERSENNE31)}
if ACC_WINDOW[P_DEFAULT] != 2048:  # the documented p = 2²⁶−5 contract
    raise InvariantError(
        f"acc_window(P_DEFAULT) = {ACC_WINDOW[P_DEFAULT]}, expected 2048: "
        f"the chunk-then-fold contract the kernels are certified against")


def is_prime(n: int) -> bool:
    if n < 2:
        return False
    for q in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        if n % q == 0:
            return n == q
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for a in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37):
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


if not (is_prime(P_DEFAULT) and is_prime(P_MERSENNE31)):
    raise InvariantError("a shipped field modulus is composite")


@dataclasses.dataclass(frozen=True)
class Field:
    """A prime field F_p with fixed-point encode/decode for real data."""

    p: int = P_DEFAULT
    frac_bits: int = 8  # fixed-point fractional bits for float <-> field

    def __post_init__(self):
        if not is_prime(self.p):
            raise ValueError(f"{self.p} is not prime")

    # ----------------------------------------------------------- modular ops
    def add(self, a, b):
        return (a + b) % self.p

    def sub(self, a, b):
        return (a - b) % self.p

    def mul(self, a, b):
        return (a.astype(jnp.int64) * b.astype(jnp.int64)) % self.p

    def neg(self, a):
        return (-a) % self.p

    def pow_scalar(self, base: int, exp: int) -> int:
        return pow(int(base) % self.p, int(exp), self.p)

    def inv_scalar(self, a: int) -> int:
        a = int(a) % self.p
        if a == 0:
            raise ZeroDivisionError("0 has no inverse")
        return pow(a, self.p - 2, self.p)

    # ------------------------------------------------------------ mod matmul
    def matmul(self, a, b):
        """Exact ``(a @ b) mod p``: the int8 limb GEMM of the served path.

        ``a: [..., M, K]``, ``b: [..., K, N]`` field elements in ``[0, p)``.
        """
        return field_matmul(a, b, p=self.p)

    # ---------------------------------------------------------- fixed point
    @property
    def scale(self) -> int:
        return 1 << self.frac_bits

    @property
    def half(self) -> int:
        return self.p // 2

    def encode(self, x):
        """Real -> field, two's-complement style: [-p/2, p/2) ↦ [0, p)."""
        q = jnp.round(jnp.asarray(x, jnp.float64) * self.scale).astype(jnp.int64)
        return q % self.p

    def decode(self, a, *, products: int = 1):
        """Field -> real.  ``products`` = #fixed-point multiplications folded
        into the value (each adds ``frac_bits`` of scale)."""
        a = jnp.asarray(a, jnp.int64) % self.p
        signed = jnp.where(a > self.half, a - self.p, a)
        return signed.astype(jnp.float64) / float(self.scale ** products)

    # --------------------------------------------------------------- random
    def random(self, key, shape):
        """Uniform field elements (secret masks)."""
        return jax.random.randint(key, shape, 0, self.p, dtype=jnp.int64)


DEFAULT_FIELD = Field()
