"""The plain reference of a served product, and its control.

The system computes ``Y = Q(A)·Q(B)`` exactly over ``F_p``, where ``Q``
rounds a float to ``frac_bits`` fractional bits (``Field.encode``), and
returns ``Y / 2^{2f}`` (``Field.decode``).  The reference forms the same
fixed-point product on the host in float64, which is exact for integers
below 2⁵³, and the comparison reads the largest gap between the served
``Y·2^{2f}`` and that integer product: 0 when the product is exact.

The control puts the same reference in the program's place computed one
precision lower: the integer operands held in bfloat16 and multiplied on
the device with float32 accumulation.

Independent of the code under test: nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

#: float32 holds every integer below 2²⁴ exactly: the served float32
#: result can be exact only while the fixed-point product stays below it
F32_EXACT = 1 << 24


def quantize(x, frac_bits: int) -> np.ndarray:
    """``round(x·2^f)`` as float64 integers (ties to even, as the field's
    encode rounds)."""
    return np.rint(np.asarray(x, np.float64) * float(1 << frac_bits))


def exact_product(a, b, frac_bits: int, p: int) -> np.ndarray:
    """``Q(a) @ Q(b)`` as exact float64 integers.

    Raises when the product leaves the range in which the served float32
    result and the field's signed decode can both be exact."""
    qa, qb = quantize(a, frac_bits), quantize(b, frac_bits)
    bound = np.abs(qa).max(initial=0) * np.abs(qb).max(initial=0) * qa.shape[-1]
    if bound >= 2.0 ** 53:
        raise ValueError("operands too large for an exact float64 product")
    ref = qa @ qb
    top = np.abs(ref).max(initial=0)
    if top >= min(F32_EXACT, p // 2):
        raise ValueError(f"fixed-point product reaches {top:.0f}: no exact "
                         "float32 result or signed decode exists")
    return ref


#: the gap of an answer that is no product of these shapes, or not finite
#: (a finite number, so the result line stays valid JSON)
NO_PRODUCT = 2.0 ** 64


def fixed_point_gap(y, ref: np.ndarray, frac_bits: int) -> float:
    """Largest ``|y·2^{2f} − ref|`` over all entries (0 when exact)."""
    scaled = np.asarray(y, np.float64) * float(1 << (2 * frac_bits))
    if scaled.shape != ref.shape:
        return NO_PRODUCT
    gap = np.abs(scaled - ref)
    return float(gap.max(initial=0)) if np.isfinite(gap).all() else NO_PRODUCT


def control_operand(x, frac_bits: int):
    """``Q(x)`` rounded to bfloat16, on the device.  Kept as its own
    program: XLA may skip a float32 → bfloat16 → float32 round trip
    inside one program (excess precision), which on the TPU left the
    control exact for ``[1, K]`` products."""
    import jax.numpy as jnp

    return jnp.round(jnp.asarray(x, jnp.float32) * float(1 << frac_bits)
                     ).astype(jnp.bfloat16)


def control_product(qa, qb, frac_bits: int):
    """The reference one precision lower: the bfloat16 operands of
    :func:`control_operand` multiplied with float32 accumulation, scaled
    back."""
    import jax.numpy as jnp

    return (jnp.matmul(qa, qb, preferred_element_type=jnp.float32)
            / float(1 << (2 * frac_bits)))
