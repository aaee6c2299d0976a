"""Pallas RWKV-6 (Finch) WKV kernel — data-dependent-decay linear attention.

    state_t = diag(exp(-exp(w_t))) · state_{t-1} + k_tᵀ v_t
    out_t   = r_t · (state_{t-1} + diag(u) · k_tᵀ v_t)

This is the sub-quadratic path that makes the ``long_500k`` shape feasible
for rwkv6-1.6b / jamba: O(T·K·V) work, O(K·V) state.  TPU schedule: grid
``(B·H, T/bt)`` with the [K, V] state resident in VMEM scratch across time
blocks (the recurrence is sequential in T — marked "arbitrary" — while B·H
is embarrassingly parallel).  Inside a block the T-loop runs on the VPU with
rank-1 outer products; K and V are lane-dim sized (64/128) so the state tile
is MXU/VPU aligned.

Note the kernel computes the *paper-faithful* recurrence (out_t uses
state_{t-1}); the oracle is :func:`repro.kernels.ref.rwkv6_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import pallas_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref,
                *, bt: int):
    tblk = pl.program_id(1)

    @pl.when(tblk == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)                      # [K]

    def body(i, _):
        r_t = r_ref[0, i].astype(jnp.float32)             # [K]
        k_t = k_ref[0, i].astype(jnp.float32)             # [K]
        v_t = v_ref[0, i].astype(jnp.float32)             # [V]
        w_t = w_ref[0, i].astype(jnp.float32)             # [K]
        kv = k_t[:, None] * v_t[None, :]                  # [K, V] rank-1
        state = state_ref[...]
        out = jnp.einsum("k,kv->v", r_t, state + u[:, None] * kv)
        decay = jnp.exp(-jnp.exp(w_t))
        state_ref[...] = state * decay[:, None] + kv
        o_ref[0, i] = out.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bt, body, 0)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rwkv6(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    bt: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """r,k,w: [B, T, H, K]; v: [B, T, H, V]; u: [H, K] → [B, T, H, V]."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    bt_ = min(bt, t)
    tp = -(-t // bt_) * bt_

    def fold(x):  # [B,T,H,D] -> [B*H, Tp, D]
        x = x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.tile(u, (b, 1))                              # [B*H, K]
    grid = (b * h, tp // bt_)
    out = pl.pallas_call(
        functools.partial(_wkv_kernel, bt=bt_),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt_, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bt_, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bt_, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bt_, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, dk), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt_, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(rf, kf, vf, wf, uf)
    out = out[:, :t].reshape(b, h, t, dv).transpose(0, 2, 1, 3)
    return out
