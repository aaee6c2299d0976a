"""Jit'd public wrappers for the Pallas kernels, with jnp fallbacks.

``use_pallas`` toggles between the Pallas kernel and the pure-jnp path;
model code calls only these.  ``interpret=None`` leaves the choice to
:func:`repro.runtime.pallas_interpret` (compiled on TPU, interpreted
elsewhere).
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .modmatmul import modmatmul as _modmatmul_pallas
from .polyeval import polyeval as _polyeval_pallas
from .rwkv6 import rwkv6 as _rwkv6_pallas


def mod_matmul(a, b, *, p: int, use_pallas: bool = False,
               interpret: bool | None = None, **block_kw):
    """Finite-field matmul (phase-2 hot loop)."""
    if use_pallas:
        return _modmatmul_pallas(a, b, p=p, interpret=interpret, **block_kw)
    return ref.modmatmul_ref(a, b, p=p)


def poly_eval(vand, terms, *, p: int, use_pallas: bool = False,
              interpret: bool | None = None, **block_kw):
    """Share evaluation F[n] = Σ_k V[n,k]·T[k] mod p (phases 1-2)."""
    if use_pallas:
        return _polyeval_pallas(vand, terms, p=p, interpret=interpret,
                                **block_kw)
    return ref.polyeval_ref(vand, terms, p=p)


def attention(q, k, v, *, causal: bool = True, use_pallas: bool = False,
              interpret: bool | None = None, **block_kw):
    """GQA attention; Pallas flash path or jnp reference path."""
    if use_pallas:
        return _flash_pallas(q, k, v, causal=causal, interpret=interpret,
                             **block_kw)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def wkv6(r, k, v, w, u, *, use_pallas: bool = False,
         interpret: bool | None = None, **block_kw):
    """RWKV-6 recurrence; Pallas scan path or jnp lax.scan reference."""
    if use_pallas:
        return _rwkv6_pallas(r, k, v, w, u, interpret=interpret, **block_kw)
    return ref.rwkv6_ref(r, k, v, w, u)
