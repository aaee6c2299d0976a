"""Pallas finite-field matmul — the phase-2 worker hot loop.

``O = (A @ B) mod p`` for field elements (int64 storage, values < p).

TPU adaptation (DESIGN.md §3): the field ``p = 2²⁶ − 5`` is chosen so a
*chunk-then-fold* schedule is exact — products are < 2⁵², so a K-block of up
to ``acc_window(p)`` MACs accumulates in int64 without overflow; one Barrett
fold (:func:`repro.kernels.barrett.mod_p` — multiply-shift, no integer
division) per K-block keeps the running accumulator < p.  Blocks are
MXU/VMEM shaped (128-aligned tiles); the fold happens on the resident output
tile in VMEM so partial sums never round-trip to HBM.  The accumulation
window is NOT hard-coded here: it derives from
:func:`repro.mpc.field.acc_window`, the single source of truth shared with
``field.ACC_WINDOW`` and the fused jnp path.  (For the Mersenne-31 field the
same schedule runs on 8-bit-limb MXU matmuls — see DESIGN.md; this kernel is
the p < 2²⁶ fast path.)

Two entry points:

* :func:`modmatmul` — one ``[M, K] @ [K, N]`` product.
* :func:`modmatmul_batched` — all N workers' ``H(α_n) = F_A(α_n)·F_B(α_n)``
  in ONE ``pallas_call``, the worker index as leading grid dimension; this
  is what ``AGECMPCProtocol.run(mode="pallas")`` uses for phase 2.

Validated against :func:`repro.kernels.ref.modmatmul_ref` in interpret mode.
Mosaic refuses both entry points on TPU (``'tpu.matmul' op Expected matmul
acc to be 32-bit``: the int64 accumulator), so ``mode="pallas"`` refuses to
run there (:meth:`repro.mpc.protocol.AGECMPCProtocol.run`).
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


def _modmatmul_kernel(a_ref, b_ref, o_ref, *, p: int, n_k: int):
    """One (bm × bn) output tile; grid dim 2 walks the K blocks.

    The output tile stays resident in VMEM across the K loop (same (i, j)
    index for every k), acting as the modular accumulator.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    b = b_ref[...]
    # exact: a,b < p  =>  bk <= acc_window(p) products + acc (< p per
    # entry) stay inside int64; one Barrett fold per K block.
    prod = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int64
    )
    o_ref[...] = mod_p(o_ref[...] + prod, p)  # fold once per K block


def _modmatmul_batched_kernel(a_ref, b_ref, o_ref, *, p: int, n_k: int):
    """Batched variant: grid dim 0 is the worker index, dim 3 the K blocks."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[0]          # [bm, bk]
    b = b_ref[0]          # [bk, bn]
    prod = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int64
    )
    o_ref[0] = mod_p(o_ref[0] + prod, p)


def _pick_blocks(m, n, k, bm, bn, bk, p):
    # The interval-analysis certificate (repro.analysis.overflow) derives
    # the largest provably-safe K block independently of acc_window's
    # closed form; the two must agree, so the kernel consumes the proof.
    # Lazy import: repro.kernels.__init__ imports this module, and the
    # verifier imports repro.kernels.barrett.
    from ..analysis.overflow import certified_bk
    window = certified_bk(p)
    if window != acc_window(p):
        raise ValueError(
            f"certified_bk({p})={window} disagrees with acc_window="
            f"{acc_window(p)}: the overflow certificate and the closed "
            "form diverged — refuse to pick a block size")
    if bk is None:
        bk = min(512, window)   # VMEM-sized default, clamped to the window
    if bk > window:
        raise ValueError(
            f"bk={bk} > acc_window({p})={window}: the int64 chunk-then-fold "
            "accumulator would overflow (certified by "
            "repro.analysis.overflow.certified_bk)")
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    mp = -(-m // bm_) * bm_
    np_ = -(-n // bn_) * bn_
    kp = -(-k // bk_) * bk_
    return bm_, bn_, bk_, mp, np_, kp


@functools.partial(
    jax.jit, static_argnames=("p", "bm", "bn", "bk", "interpret")
)
def modmatmul(
    a: jax.Array,
    b: jax.Array,
    *,
    p: int,
    bm: int = 128,
    bn: int = 128,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``(a @ b) mod p`` with explicit VMEM tiling.

    ``a: [M, K]``, ``b: [K, N]`` int64 field elements; shapes need not be
    block multiples (padded here, sliced on return).  ``bk`` must respect
    the field's exact accumulation window (``acc_window(p)``).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ShapeContractError(
            f"modmatmul inner dims disagree: {a.shape} @ {b.shape}",
            shapes=(a.shape, b.shape))
    bm_, bn_, bk_, mp, np_, kp = _pick_blocks(m, n, k, bm, bn, bk, p)
    a = jnp.pad(a.astype(jnp.int64), ((0, mp - m), (0, kp - k)))
    b = jnp.pad(b.astype(jnp.int64), ((0, kp - k), (0, np_ - n)))
    grid = (mp // bm_, np_ // bn_, kp // bk_)
    out = pl.pallas_call(
        functools.partial(_modmatmul_kernel, p=p, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int64),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(a, b)
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("p", "bm", "bn", "bk", "interpret")
)
def modmatmul_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    p: int,
    bm: int = 128,
    bn: int = 128,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``(a[w] @ b[w]) mod p`` for every worker ``w`` in ONE ``pallas_call``.

    ``a: [W, M, K]``, ``b: [W, K, N]`` int64 field elements.  The worker
    index is the leading grid dimension, so all N workers' phase-2 products
    execute as one block program — no host-side loop, no per-worker dispatch
    (DESIGN.md §3).  Same chunk-then-fold exactness contract as
    :func:`modmatmul`.
    """
    w, m, k = a.shape
    w2, k2, n = b.shape
    if (w, k) != (w2, k2):
        raise ShapeContractError(
            f"batched modmatmul operands disagree: {a.shape} @ {b.shape}",
            shapes=(a.shape, b.shape))
    bm_, bn_, bk_, mp, np_, kp = _pick_blocks(m, n, k, bm, bn, bk, p)
    a = jnp.pad(a.astype(jnp.int64), ((0, 0), (0, mp - m), (0, kp - k)))
    b = jnp.pad(b.astype(jnp.int64), ((0, 0), (0, kp - k), (0, np_ - n)))
    grid = (w, mp // bm_, np_ // bn_, kp // bk_)
    out = pl.pallas_call(
        functools.partial(_modmatmul_batched_kernel, p=p, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), lambda ww, i, j, kk: (ww, i, kk)),
            pl.BlockSpec((1, bk_, bn_), lambda ww, i, j, kk: (ww, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda ww, i, j, kk: (ww, i, j)),
        out_shape=jax.ShapeDtypeStruct((w, mp, np_), jnp.int64),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(a, b)
    return out[:, :m, :n]
