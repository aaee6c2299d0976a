"""Pallas blocked causal attention with online softmax (GQA-aware).

Grid ``(batch, q_head, q_block, kv_block)``; the output tile plus the running
(max, sum) statistics stay resident in VMEM scratch across the kv_block loop
(standard FlashAttention-2 schedule re-expressed for the TPU: MXU-shaped
128×128 q/k tiles, softmax statistics on the VPU, no HBM round-trip for the
accumulator).  Causal blocks strictly above the diagonal are skipped via
``pl.when`` — with the kv grid dim marked "arbitrary" this is the TPU
equivalent of the CUDA early-exit.

GQA: the q→kv head mapping happens in the BlockSpec index_map
(``hq // group``), so KV tiles are fetched once per q-head group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..mpc.errors import ShapeContractError
from ..runtime import pallas_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, bq: int, bk: int,
                  n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block strictly above the diagonal contributes nothing
    run = (not causal) or (ik * bk < (iq + 1) * bq)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [B, T, Hq, D]; k, v: [B, S, Hkv, D]; Hq % Hkv == 0 → [B, T, Hq, D]."""
    b, tq, hq, d = q.shape
    _, s, hkv, _ = k.shape
    if hq % hkv:
        raise ShapeContractError(
            f"GQA needs Hq divisible by Hkv: got Hq={hq}, Hkv={hkv}",
            shapes=(q.shape, k.shape))
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bq_, bk_ = min(bq, tq), min(bk, s)
    tp = -(-tq // bq_) * bq_
    sp = -(-s // bk_) * bk_
    # layout: [B, H, T, D] blocks
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, tp - tq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    grid = (b, hq, tp // bq_, sp // bk_)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal,
            bq=bq_, bk=bk_, n_kv=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk_, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk_, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, d), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, d), jnp.float32),   # acc
            pltpu.VMEM((bq_, 1), jnp.float32),   # running max
            pltpu.VMEM((bq_, 1), jnp.float32),   # running sum
        ],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(qt, kt, vt)
    return out[:, :, :tq].transpose(0, 2, 1, 3)
