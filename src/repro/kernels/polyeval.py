"""Pallas share-evaluation kernel — phase-1 / phase-2 polynomial points.

Computes ``F[n, :] = (Σ_k V[n, k] · T[k, :]) mod p`` — every worker's share
is a Vandermonde-weighted sum of the coded+secret term blocks (eqs. (3)-(7)
after flattening each m/t × m/s block).  Same algebra as a matmul but a very
different shape regime: K = ts+z terms is tiny (tens), N_workers is small
(tens..hundreds), and the trailing dim is the flattened block (large).  The
kernel therefore keeps the whole K dimension resident and walks (worker-block
× column-block) tiles — one Barrett fold (:func:`repro.kernels.barrett.mod_p`)
at the end, no K loop.

The same shape regime covers the phase-2 exchange (``G``-mix: ``g_mix.T @
H-points``) and the phase-3 decode (``V⁻¹ rows @ I-points``), so
``AGECMPCProtocol.run(mode="pallas")`` routes all three through this kernel.

Exactness: K must fit one accumulation window — ``K ≤ acc_window(p)``
(:func:`repro.mpc.field.acc_window`, the shared contract; 2048 for the
default prime, always true for K = ts + z).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mpc.errors import ShapeContractError
from ..mpc.field import acc_window
from ..runtime import pallas_interpret
from .barrett import mod_p


def _polyeval_kernel(v_ref, t_ref, o_ref, *, p: int):
    v = v_ref[...]          # [bn, K]
    t = t_ref[...]          # [K, bc]
    acc = jax.lax.dot_general(
        v, t, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int64
    )
    o_ref[...] = mod_p(acc, p)


@functools.partial(jax.jit, static_argnames=("p", "bn", "bc", "interpret"))
def polyeval(
    vand: jax.Array,
    terms: jax.Array,
    *,
    p: int,
    bn: int = 8,
    bc: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``vand: [N, K]`` (α powers), ``terms: [K, C]`` (flattened blocks).

    Returns ``[N, C]`` shares.  K must be ≤ ``acc_window(p)`` (one exact
    int64 window — always true for the protocol's K = ts + z); larger K
    belongs to the chunked :func:`repro.kernels.modmatmul.modmatmul` path."""
    n, k = vand.shape
    k2, c = terms.shape
    if k != k2:
        raise ShapeContractError(
            f"polyeval needs vand [N,K] @ terms [K,C]: got {vand.shape} "
            f"and {terms.shape}", shapes=(vand.shape, terms.shape))
    window = acc_window(p)
    if k > window:
        raise ValueError(
            f"K={k} > acc_window({p})={window}: use the chunked modmatmul path")
    bn_, bc_ = min(bn, n), min(bc, c)
    np_, cp = -(-n // bn_) * bn_, -(-c // bc_) * bc_
    vand = jnp.pad(vand.astype(jnp.int64), ((0, np_ - n), (0, 0)))
    terms = jnp.pad(terms.astype(jnp.int64), ((0, 0), (0, cp - c)))
    grid = (np_ // bn_, cp // bc_)
    out = pl.pallas_call(
        functools.partial(_polyeval_kernel, p=p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn_, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bc_), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn_, bc_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, cp), jnp.int64),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(vand, terms)
    return out[:n, :c]
