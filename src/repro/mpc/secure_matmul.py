"""Distributed AGE-CMPC: the worker pool mapped onto a mesh axis.

The paper's N edge workers become N logical workers packed onto a named mesh
axis (round-robin, padded).  Phase-2's worker↔worker exchange of
``G_n(α_{n'})`` -- the dominant communication, eq. (17) -- is exactly one
``psum_scatter`` over that axis: every device reduces its local workers'
contributions to every I(α_{n'}) and receives back only its own n' chunk.
That is the TPU-native form of the paper's all-pairs exchange (DESIGN.md §3).

``secure_matmul`` is the composable entry point used by the model zoo's MPC
mode: float in, float out, everything in between in F_p.  Protocol plans
(alphas, Vandermonde tables, G-mix) resolve through the process-wide
:mod:`repro.mpc.planner` cache (DESIGN.md §2), so repeated sharded or
single-process instances of the same parameterization never rebuild them;
the single-process path additionally reuses a per-plan jit-compiled fused
runner (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.barrett import field_matmul, mod_p
from ..runtime import span
from .api import MPCSpec
from .field import Field
from .protocol import AGECMPCProtocol


def _pad_to(x: np.ndarray, n: int, axis: int = 0) -> np.ndarray:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths)


def _runs(xs):
    """``(first, last)`` of each run of consecutive integers in sorted ``xs``."""
    out = []
    for x in xs:
        if out and out[-1][1] == x - 1:
            out[-1] = (out[-1][0], x)
        else:
            out.append((x, x))
    return out


def mod_ring_reduce_scatter(x, axis: str, p: int, n_shards: int):
    """Reduce-scatter of field elements with per-hop modular folding.

    A plain ``psum_scatter`` must carry int64 (a 256-way sum of values < p
    overflows int32); folding ``mod p`` at every ring hop keeps the payload
    int32 — **half the wire bytes** of the int64 collective.  This is the
    TPU-native "modular collective" form of the paper's phase-2 exchange
    (beyond-paper optimization; see EXPERIMENTS.md §Perf).

    ``x: [n_shards * chunk, ...]`` int32 field elements (already < p).
    Returns this shard's reduced chunk ``[chunk, ...]``.
    """
    me = jax.lax.axis_index(axis)
    chunks = x.reshape((n_shards, -1) + x.shape[1:])
    if n_shards == 1:
        return chunks[0]
    perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]

    def my_chunk(s):
        return jax.lax.dynamic_index_in_dim(
            chunks, (me + 1 + s) % n_shards, axis=0, keepdims=False)

    def body(s, acc):
        acc = jax.lax.ppermute(acc, axis, perm)
        folded = (acc.astype(jnp.int64)
                  + my_chunk(s).astype(jnp.int64)) % p
        return folded.astype(acc.dtype)

    # acc starts as chunk (me+1); after n-1 hops it is Σ over all shards of
    # chunk `me` (verified in tests against psum_scatter)
    acc = my_chunk(0)
    return jax.lax.fori_loop(1, n_shards, body, acc)


@dataclasses.dataclass(frozen=True)
class ShardedCMPC:
    """One protocol instance bound to a mesh axis.

    Workers ``0..N-1`` are padded to ``N_pad`` (a multiple of the axis size)
    and laid out worker-major so device d owns workers
    ``d·(N_pad/D) .. (d+1)·(N_pad/D)-1``.  Padded workers have all-zero
    Vandermonde rows: they contribute nothing to the scattered reduction.
    Phases 1-2 run on the mesh; the I points stay on the chips, and the
    decode quorum's ``t²+z`` rows go chip to chip to the master's device
    (:attr:`decode_device`) for the decode.  No share crosses the host.

    Optimization knobs (paper-faithful defaults; see EXPERIMENTS.md §Perf):

    * ``wire_dtype``: "int64" (baseline) or "int32" — field elements fit 31
      bits; int32 halves argument/HBM/wire bytes.  The exchange then uses
      :func:`mod_ring_reduce_scatter` (per-hop mod fold) instead of one
      ``psum_scatter`` of 16-bit halves.
    * ``prg_masks``: derive phase-2 masks R_w^{(n)} on-device from per-worker
      PRNG keys instead of shipping ~z·m²/t² scalars per worker from the
      host (PRG-based masking, standard MPC practice).
    """

    proto: AGECMPCProtocol
    mesh: Mesh
    axis: str = "model"
    wire_dtype: str = "int64"
    prg_masks: bool = False
    #: bytes counted by :meth:`run`: through the host (none), and of
    #: quorum rows copied chip to chip to the decode device
    counters: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"host_bytes": 0, "mesh_bytes": 0},
        init=False, compare=False, repr=False)

    @classmethod
    def from_spec(cls, spec: MPCSpec, mesh: Mesh, *, axis: str = "model",
                  m: Optional[int] = None, **kw) -> "ShardedCMPC":
        """A sharded runner for one unified spec (block side ``m`` or
        ``spec.m``); ``kw`` passes the optimization knobs through."""
        return cls(AGECMPCProtocol.from_spec(spec, m=m), mesh, axis, **kw)

    @property
    def spec(self) -> MPCSpec:
        return self.proto.spec

    @property
    def axis_size(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def n_pad(self) -> int:
        d = self.axis_size
        return -(-self.proto.n_workers // d) * d

    # ------------------------------------------------------ padded constants
    def _padded(self, arr: np.ndarray, axes=(0,)) -> jnp.ndarray:
        out = arr
        for ax in axes:
            out = _pad_to(out, self.n_pad, axis=ax)
        return jnp.asarray(out)

    def _consts(self):
        pr = self.proto
        return dict(
            vand_a=self._padded(pr.vand_a),           # [Np, ts+z]
            vand_b=self._padded(pr.vand_b),           # [Np, ts+z]
            g_mix=self._padded(pr.g_mix, axes=(0, 1)),  # [Np, Np']
            vand_g=self._padded(pr.vand_g_secret),    # [Np, z]
        )

    # -------------------------------------------------------------- the step
    def build_step(self):
        """Returns jitted ``step(terms_a, terms_b, masks) -> I points [Np,...]``.

        * ``terms_a: [ts+z, m/t, m/s]`` -- Aᵀ blocks ++ secret blocks
          (replicated: every device evaluates its own workers' shares).
        * ``masks``: per-worker phase-2 masks R_w^{(n)} [Np, z, m/t, m/t]
          (baseline), or per-worker PRNG keys [Np, 2] when ``prg_masks``.
        """
        pr = self.proto
        p = pr.field.p
        c = self._consts()
        axis = self.axis
        n_shards = self.axis_size
        wire = jnp.dtype(self.wire_dtype)
        prg = self.prg_masks
        z, mt = pr.z, pr.m // pr.t
        spec_w = P(axis)       # worker-sharded leading axis
        spec_r = P()           # replicated

        if wire == jnp.int32:
            c = {k: v.astype(jnp.int32) for k, v in c.items()}

        def step(terms_a, terms_b, masks):
            def exchange(g_mix, vand_g, h, mk):
                nl = h.shape[0]
                # phase 2 exchange: G contributions for every n', then scatter
                g_all = field_matmul(g_mix.T, h.reshape(nl, -1), p=p)
                if prg:
                    # derive local workers' masks from their keys on device:
                    # raw 64-bit stream mod p (bias 2⁻³⁸) — one generate pass
                    # + one fold pass, far cheaper than randint's rejection
                    # machinery (measured in §Perf; the int64 randint variant
                    # was refuted)
                    def mask_of(key):
                        bits = jax.random.bits(key, (z, mt, mt), jnp.uint64)
                        return (bits % jnp.uint64(p)).astype(jnp.int64)

                    mk_local = jax.vmap(mask_of)(mk)                # [nl,z,...]
                else:
                    mk_local = mk.astype(jnp.int64)
                # Σ_n Σ_w vand_g[n', w]·R_w^{(n)} = vand_g · (Σ_n R^{(n)})
                mk_sum = mod_p(jnp.sum(mk_local, axis=0), p)      # [z, ...]
                g_all = mod_p(g_all + field_matmul(
                    vand_g, mk_sum.reshape(z, -1), p=p), p)
                g_all = g_all.reshape((-1, mt, mt))                 # [Np', ...]
                if wire == jnp.int32:
                    i_local = mod_ring_reduce_scatter(
                        g_all.astype(jnp.int32), axis, p, n_shards)
                    return i_local.astype(jnp.int64).reshape(
                        (-1,) + g_all.shape[1:])
                # XLA:TPU has no int64 reduce-scatter: carry each residue
                # (< 2³¹) as two 16-bit halves in int32 lanes, whose sums
                # over ≤ 2¹⁵ devices cannot overflow, and rejoin them here
                halves = jnp.stack([g_all & 0xFFFF, g_all >> 16],
                                   axis=-1).astype(jnp.int32)
                lo, hi = jnp.moveaxis(jax.lax.psum_scatter(
                    halves, axis, scatter_dimension=0, tiled=True
                ).astype(jnp.int64), -1, 0)
                return mod_p(lo + (hi << 16), p)

            def local(vand_a, vand_b, g_mix, vand_g, ta, tb, mk):
                nl = vand_a.shape[0]
                # phase 1 (local workers' shares)
                with jax.named_scope("mpc.encode"):
                    f_a = field_matmul(vand_a, ta.reshape(ta.shape[0], -1),
                                       p=p)
                    f_b = field_matmul(vand_b, tb.reshape(tb.shape[0], -1),
                                       p=p)
                # phase 2 compute: H(α_n) = F_A·F_B
                with jax.named_scope("mpc.worker_compute"):
                    h = field_matmul(f_a.reshape((nl,) + ta.shape[1:]),
                                     f_b.reshape((nl,) + tb.shape[1:]), p=p)
                with jax.named_scope("mpc.exchange"):
                    return exchange(g_mix, vand_g, h, mk)

            return shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec_w, spec_w, P(axis, None), spec_r,
                          spec_r, spec_r, spec_w),
                out_specs=spec_w,
            )(c["vand_a"], c["vand_b"], c["g_mix"], c["vand_g"],
              terms_a, terms_b, masks)

        return jax.jit(step)

    @cached_property
    def _step(self):
        """The compiled step, built once per runner (not per block)."""
        return self.build_step()

    @property
    def decode_device(self):
        """The master's chip: the mesh's first device, which holds
        workers ``0 .. N_pad/D-1`` and runs the decode."""
        return self.mesh.devices.flat[0]

    @cached_property
    def _quorum_idx(self):
        """The identity index over the ``t²+z`` gathered quorum rows."""
        return jnp.arange(self.proto.recovery_threshold)

    def run(self, a, b, key, *, survivors: Optional[np.ndarray] = None):
        """Full distributed run: phases 1-2 on the mesh, the decode on
        :attr:`decode_device`.

        Only the decode quorum's ``t²+z`` I-point rows leave the chips
        that computed them, chip to chip (:meth:`gather_quorum`); nothing
        crosses the host, and nothing here waits for the device, so the
        next block dispatches while this one runs."""
        pr = self.proto
        idx = pr.survivor_prefix(survivors)
        with span("sharded.shares"):
            i_pts = self.shares(a, b, key)
        with span("sharded.gather"):
            rows = self.gather_quorum(i_pts, idx)
        with span("sharded.decode"):
            _, dec_rows = pr.plan.survivor_tables(tuple(idx))
            return pr.plan.stages().decode(rows, self._quorum_idx, dec_rows)

    def gather_quorum(self, i_pts, idx) -> jnp.ndarray:
        """The I-point rows ``idx`` (ascending), stacked on
        :attr:`decode_device`.

        Each chip's contiguous runs of quorum rows are sliced where they
        live; the slices of other chips are copied to the decode device,
        and their bytes add to ``counters["mesh_bytes"]``.  Where the
        worker axis is replicated over other mesh axes, the decode
        device's own replica is read first."""
        dev = self.decode_device
        owner = {}                     # first row of a shard -> its shard
        for sh in i_pts.addressable_shards:
            start = sh.index[0].start or 0
            if start not in owner or sh.device == dev:
                owner[start] = sh
        pieces = []
        for start, sh in sorted(owner.items()):
            local = [int(r) - start for r in idx
                     if 0 <= int(r) - start < sh.data.shape[0]]
            for first, last in _runs(local):
                rows = sh.data[first: last + 1]
                if sh.device != dev:
                    rows = jax.device_put(rows, dev)
                    self.counters["mesh_bytes"] += rows.nbytes
                pieces.append(rows)
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    def shares(self, a, b, key):
        """Phases 1-2 on the mesh: the I points ``[N_pad, m/t, m/t]``,
        sharded worker-major over ``axis``."""
        pr = self.proto
        k1a, k1b, k2 = jax.random.split(key, 3)
        sec_a = pr.field.random(
            k1a, (pr.z, pr.m // pr.t, pr.m // pr.s))
        sec_b = pr.field.random(
            k1b, (pr.z, pr.m // pr.s, pr.m // pr.t))
        terms_a = jnp.concatenate([pr._split_a(a), sec_a])
        terms_b = jnp.concatenate([pr._split_b(b), sec_b])
        if self.prg_masks:
            masks = jax.vmap(jax.random.fold_in, (None, 0))(
                k2, jnp.arange(self.n_pad))
        else:
            masks = pr.field.random(
                k2, (self.n_pad, pr.z, pr.m // pr.t, pr.m // pr.t))
        if self.wire_dtype == "int32" and not self.prg_masks:
            masks = masks.astype(jnp.int32)
        if self.wire_dtype == "int32":
            terms_a = terms_a.astype(jnp.int32)
            terms_b = terms_b.astype(jnp.int32)
        return self._step(terms_a, terms_b, masks)


# ------------------------------------------------------------- float facade
def secure_matmul(a, b, *, s: int, t: int, z: int,
                  field: Optional[Field] = None,
                  mesh: Optional[Mesh] = None, axis: str = "model",
                  key=None, scheme: str = "age"):
    """``AᵀB`` for real-valued square ``a, b`` via CMPC (legacy shim).

    Thin delegation to the unified session API
    (:func:`repro.mpc.connect`): the spec pins the block side to
    ``a.shape[0]``, so the session maps the call onto exactly one coded
    block consuming ``key`` directly — bit-identical to the historical
    ``encode → AGECMPCProtocol.run → decode`` pipeline.  With ``mesh``
    given, phases 1-2 run sharded over ``axis``; otherwise the
    single-process simulation is used (CI/CPU).  New code should call
    ``connect(spec).matmul`` — it also accepts rectangular and batched
    operands.
    """
    from .api import connect

    a = jnp.asarray(a)
    spec = MPCSpec(s=s, t=t, z=z, scheme=scheme, m=int(a.shape[0]),
                   **({"field": field} if field else {}))
    if mesh is not None:
        sess = connect(spec, backend="sharded", mesh=mesh, axis=axis)
    else:
        sess = connect(spec, backend="local")
    key = key if key is not None else jax.random.PRNGKey(0)
    return sess.matmul(a.T, b, key=key).astype(a.dtype)
