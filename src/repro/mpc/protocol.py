"""Executable AGE-CMPC (paper §IV-B): the three phases, end to end.

The same machinery also runs Entangled-CMPC (λ=0) and PolyDot-CMPC (the
generalized-code parameterization), so the baselines the paper compares
against are executable too, not just counted.

Two runners:

* :meth:`AGECMPCProtocol.run` -- single-process simulation (tests, CPU).
* :mod:`repro.mpc.secure_matmul` -- shard_map runner mapping the worker pool
  onto a mesh axis (phase-2 exchange = one ``psum_scatter``).

Straggler / fault tolerance: phase 3 decodes from ANY ``t²+z`` surviving
workers (coded redundancy = the paper's headline property, exposed here as
``decode(..., survivors=mask)``).

Fast path (DESIGN.md §2-§3, §5): all data-independent tables come from the
process-wide :mod:`repro.mpc.planner` cache, and ``run`` composes the
plan's staged jit programs (:class:`repro.mpc.planner.ProtocolStages`) —
chunk-then-fold matmuls with Barrett reduction
(:mod:`repro.kernels.barrett`) instead of per-op ``einsum … % p``.  The
default all-alive path executes the single fully-fused program; a
``survivors`` mask runs the SAME phase-1/2 program (``front``) and swaps
only the decode stage's rows in from the plan's survivor-table LRU — no
eager fallback.  ``mode="reference"`` keeps the original eager
phase-by-phase path (the bit-exactness oracle and benchmark baseline);
``mode="pallas"`` routes the heavy phases through the Pallas kernels
(:mod:`repro.kernels.modmatmul`, :mod:`repro.kernels.polyeval`) in
interpret mode; Mosaic refuses their int64 accumulators, so that mode
raises on TPU.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.age import GeneralizedPolyCode
from ..kernels.barrett import mod_p
from .. import runtime
from .api import MPCSpec
from .field import DEFAULT_FIELD, Field, acc_window
from .lagrange import inv_mod, vandermonde
from .planner import PlanKey, ProtocolPlan


def _refuse_pallas_on_tpu() -> None:
    """The Pallas field kernels accumulate in int64, which Mosaic refuses
    on TPU (``'tpu.matmul' op Expected matmul acc to be 32-bit``)."""
    if runtime.on_tpu():
        raise NotImplementedError(
            "mode='pallas' cannot run on TPU: Mosaic refuses the modmatmul/"
            "polyeval kernels' int64 accumulator ('tpu.matmul' op Expected "
            "matmul acc to be 32-bit); use the default mode='fused', whose "
            "int8 limb GEMM lowers on TPU")


@dataclasses.dataclass(frozen=True)
class AGECMPCProtocol:
    """Plan + executable phases for one ``Y = AᵀB`` under CMPC.

    Parameters
    ----------
    s, t : matrix partitions (s | m and t | m required)
    z    : collusion bound
    m    : matrix side
    lam  : AGE gap; ``None`` solves ``min_λ`` (eq. (13))
    scheme : "age" | "entangled" | "polydot"

    All data-independent tables (``alphas``, ``r_coeffs``, Vandermonde
    tables, decode rows) resolve through the shared
    :func:`repro.mpc.planner.get_plan` cache: constructing many protocol
    instances with the same parameters — one per request under serving
    traffic — costs one plan build total.
    """

    s: int
    t: int
    z: int
    m: int
    lam: Optional[int] = None
    scheme: str = "age"
    field: Field = DEFAULT_FIELD
    # heterogeneous-pool identity (DESIGN.md §8): the device roster and the
    # evaluation-point placement (roster device id per worker slot).  Both
    # are carried for grouping/attrition-routing only — the phase math and
    # the plan tables are placement-independent.
    pool: Optional[object] = None          # repro.mpc.workers.WorkerPool
    placement: Optional[tuple] = None
    # Byzantine budget a (DESIGN.md §9): carried so spec round-trips keep
    # the verified-quorum contract; a > 0 routes run() through the MAC-
    # verified decode path.  Like pool/placement it never changes the plan
    # tables — only how decode treats the shares.
    adversaries: int = 0

    def __post_init__(self):
        if self.m % self.s or self.m % self.t:
            raise ValueError(f"need s|m and t|m: s={self.s} t={self.t} m={self.m}")

    # ------------------------------------------------------------------ spec
    @classmethod
    def from_spec(cls, spec: MPCSpec, m: Optional[int] = None
                  ) -> "AGECMPCProtocol":
        """A protocol instance for one :class:`~repro.mpc.api.MPCSpec`
        at block side ``m`` (defaults to ``spec.m``)."""
        return cls(s=spec.s, t=spec.t, z=spec.z, m=spec._block(m),
                   lam=spec.lam, scheme=spec.scheme, field=spec.field,
                   pool=spec.pool, placement=spec.effective_placement,
                   adversaries=spec.adversaries)

    @cached_property
    def spec(self) -> MPCSpec:
        """This instance's parameterization as the unified spec object."""
        return MPCSpec(s=self.s, t=self.t, z=self.z, lam=self.lam,
                       scheme=self.scheme, field=self.field, m=self.m,
                       pool=self.pool, placement=self.placement,
                       adversaries=self.adversaries)

    @property
    def plan_key(self) -> PlanKey:
        """The process-wide planner-cache key (via the spec)."""
        return self.spec.plan_key()

    @property
    def group_key(self):
        """Serving-group identity: plan key + pool signature (the
        ``(plan_key, pool_key)`` grouping of DESIGN.md §8; equals the bare
        plan key for pool-free specs)."""
        return self.spec.group_key()

    # ------------------------------------------------------------------ plan
    @cached_property
    def plan(self) -> ProtocolPlan:
        """The cached data-independent tables (shared across instances)."""
        return self.spec.plan()

    @property
    def code(self) -> GeneralizedPolyCode:
        return self.plan.code

    @property
    def n_workers(self) -> int:
        return self.plan.n_workers

    @property
    def recovery_threshold(self) -> int:
        return self.plan.recovery_threshold

    @property
    def powers_h(self) -> np.ndarray:
        return self.plan.powers_h

    @property
    def alphas(self) -> np.ndarray:
        """Evaluation points: α_n = n when that yields invertible systems."""
        return self.plan.alphas

    @property
    def r_coeffs(self) -> np.ndarray:
        """r_n^{(i,l)} of eq. (9): [t², N], row u=i+t·l extracts H_{imp(i,l)}."""
        return self.plan.r_coeffs

    @property
    def vand_a(self) -> np.ndarray:
        """[N, t·s + z] powers of α_n for F_A terms (coded then secret)."""
        return self.plan.vand_a

    @property
    def vand_b(self) -> np.ndarray:
        return self.plan.vand_b

    @property
    def g_mix(self) -> np.ndarray:
        """c[n, n'] = Σ_{i,l} r_n^{(i,l)}·α_{n'}^{i+t·l} mod p  -- the scalar
        that multiplies H(α_n) inside G_n(α_{n'}) (first sum of eq. (10))."""
        return self.plan.g_mix

    @property
    def vand_g_secret(self) -> np.ndarray:
        """α_{n'}^{t²+w} for w < z (second sum of eq. (10)): [N, z]."""
        return self.plan.vand_g_secret

    # -------------------------------------------------------------- phase 1
    def _split_a(self, a):
        """Aᵀ -> [t·s, m/t, m/s] blocks, i-major (matches planner powers)."""
        t, s, m = self.t, self.s, self.m
        at = jnp.asarray(a, jnp.int64).T
        blocks = at.reshape(t, m // t, s, m // s).transpose(0, 2, 1, 3)
        return blocks.reshape(t * s, m // t, m // s)

    def _split_b(self, b):
        """B -> [s·t, m/s, m/t] blocks, k-major (matches planner powers)."""
        t, s, m = self.t, self.s, self.m
        b = jnp.asarray(b, jnp.int64)
        blocks = b.reshape(s, m // s, t, m // t).transpose(0, 2, 1, 3)
        return blocks.reshape(s * t, m // s, m // t)

    def phase1_shares(self, a, b, key):
        """Sources build F_A(α_n), F_B(α_n) for every worker n.

        Returns ``(f_a: [N, m/t, m/s], f_b: [N, m/s, m/t])``.
        """
        ka, kb = jax.random.split(key)
        sec_a = self.field.random(ka, (self.z, self.m // self.t, self.m // self.s))
        sec_b = self.field.random(kb, (self.z, self.m // self.s, self.m // self.t))
        terms_a = jnp.concatenate([self._split_a(a), sec_a])   # [ts+z, mt, ms]
        terms_b = jnp.concatenate([self._split_b(b), sec_b])   # [ts+z, ms, mt]
        va = jnp.asarray(self.vand_a)
        vb = jnp.asarray(self.vand_b)
        # (p-1)² < 2⁵²; ts+z terms ≤ ACC window for defaults -> fold once.
        f_a = jnp.einsum("nk,krc->nrc", va, terms_a) % self.field.p
        f_b = jnp.einsum("nk,krc->nrc", vb, terms_b) % self.field.p
        return f_a, f_b

    # -------------------------------------------------------------- phase 2
    def phase2_compute(self, f_a, f_b, *, use_kernel: bool = False):
        """Each worker: H(α_n) = F_A(α_n)·F_B(α_n) mod p  (the hot loop).

        ``use_kernel=True`` routes through the batched Pallas kernel (all N
        workers in one ``pallas_call``, worker index = grid dim 0; refused
        on TPU, see :meth:`run`)."""
        if use_kernel:
            from ..kernels.modmatmul import modmatmul_batched
            _refuse_pallas_on_tpu()
            return modmatmul_batched(
                jnp.asarray(f_a, jnp.int64), jnp.asarray(f_b, jnp.int64),
                p=self.field.p)
        return self.field.matmul(f_a, f_b)

    def phase2_exchange(self, h, key):
        """Workers build G_n, exchange points, sum: returns I(α_{n'}) [N,...].

        Simulated runner: the exchange collapses to two einsums (the sharded
        runner in secure_matmul.py performs the real ``psum_scatter``).
        """
        n = self.n_workers
        mt = self.m // self.t
        r_mask = self.field.random(key, (n, self.z, mt, mt))
        c = jnp.asarray(self.g_mix)               # [n, n']
        vg = jnp.asarray(self.vand_g_secret)      # [n', z]
        i_pts = jnp.einsum("nm,nrc->mrc", c, h) % self.field.p
        mask_sum = jnp.sum(r_mask, axis=0) % self.field.p        # [z, mt, mt]
        i_pts = (i_pts + jnp.einsum("mw,wrc->mrc", vg, mask_sum)) % self.field.p
        return i_pts

    # -------------------------------------------------------------- phase 3
    def survivor_prefix(self, survivors: Optional[np.ndarray]) -> np.ndarray:
        """First ``t²+z`` alive worker indices for a survivor mask.

        The public survivor-mask contract, shared with every other entry
        point through :meth:`repro.mpc.api.MPCSpec.validate_survivors`:
        raises if the mask is mis-shaped or fewer than ``t²+z`` survive
        (beyond coded tolerance).  The prefix is the decode quorum; its
        frozen tuple keys the plan's survivor-table LRU.
        """
        return self.spec.validate_survivors(survivors)

    # retired private spelling, kept for older call sites
    _survivor_prefix = survivor_prefix

    def decode(self, i_points, survivors: Optional[np.ndarray] = None):
        """Master reconstructs Y from any t²+z surviving I(α_n) points.

        ``survivors``: boolean mask [N]; defaults to all alive.  Raises if
        fewer than ``t²+z`` survive (beyond coded tolerance).

        Decode rows resolve through the plan: masks whose first ``t²+z``
        alive indices equal the default prefix (including an explicit
        all-True mask) short-circuit to the precomputed ``plan.decode_rows``;
        every other survivor set hits the plan's LRU of cached tables,
        solved on miss with the vectorized Montgomery/Gauss–Jordan path.
        The arithmetic runs through the plan's compiled decode stage — the
        same single program ``run(survivors=...)`` and the batched engine
        use, window-safe for any supported prime (DESIGN.md §3, §5).
        """
        idx = self.survivor_prefix(survivors)
        idx_j, rows_j = self.plan.survivor_tables(tuple(idx))
        return self.plan.stages().decode(
            jnp.asarray(i_points, jnp.int64), idx_j, rows_j)

    # ------------------------------------------------------------------ run
    def run(self, a, b, key, *, survivors: Optional[np.ndarray] = None,
            mode: str = "fused"):
        """All three phases; returns Y = AᵀB mod p.

        ``mode`` selects the execution path (bit-identical where defined):

        * ``"fused"`` (default) — the plan's staged jit programs
          (DESIGN.md §5).  All-alive: one fully-fused program for all three
          phases.  With a ``survivors`` mask: the SAME compiled phase-1/2
          ``front`` program, then the shared decode stage with the survivor
          rows swapped in from the plan's LRU — the mask never changes
          which programs compile, only which rows they consume.  Exact for
          any supported prime (chunked to the field window).
        * ``"pallas"`` — heavy phases through the Pallas kernels in
          interpret mode; survivor masks take the same cached-rows decode.
          Raises ``NotImplementedError`` on TPU, where Mosaic refuses the
          kernels' int64 accumulators.
        * ``"reference"`` — the original eager phase-by-phase path, ending
          in the seed's per-call object-dtype survivor solve.

        The reference and pallas paths accumulate whole term/worker sums in
        one int64 window, so they require ``acc_window(p) ≥ max(ts+z, N)``
        — true for the default prime, NOT for Mersenne-31 (window 2).
        They raise a descriptive error rather than silently overflow
        (DESIGN.md §3); use the fused default for small-window fields.
        """
        if mode not in ("fused", "pallas", "reference"):
            raise ValueError(
                f"unknown mode {mode!r}: expected fused|pallas|reference")
        if mode == "reference":
            return self.run_reference(a, b, key, survivors=survivors)
        if mode == "pallas":
            return self._run_pallas(a, b, key, survivors=survivors)
        if self.adversaries:
            # a Byzantine budget makes verification non-optional: the
            # fused path routes through MAC check + liar-excluding decode
            # (bit-identical to the honest run when nobody lies)
            return self.run_verified(a, b, key, survivors=survivors)[0]
        stages = self.plan.stages()
        a = jnp.asarray(a, jnp.int64)
        b = jnp.asarray(b, jnp.int64)
        if survivors is None:
            return stages.fused(a, b, key)
        idx = self.survivor_prefix(survivors)
        idx_j, rows_j = self.plan.survivor_tables(tuple(idx))
        i_pts = stages.front(a, b, key)
        return stages.decode(i_pts, idx_j, rows_j)

    # -------------------------------------------------- Byzantine tolerance
    def run_verified(self, a, b, key, *,
                     survivors: Optional[np.ndarray] = None,
                     injector=None, round_id: int = 0):
        """All three phases with MAC-verified decode (DESIGN.md §9).

        Returns ``(y, verdict)``: ``y`` is bit-identical to the honest
        ``run`` whenever at most ``spec.adversaries`` shares were
        corrupted — liars are localized by their failed tags, excluded,
        and the decode interpolates from the first ``t²+z`` honest
        survivors (the shares are exact evaluations of one polynomial, so
        ANY honest quorum reconstructs the same ``Y``).  ``injector``
        (a :class:`repro.mpc.byzantine.FaultInjector`) corrupts the
        shares/tags between tagging and verification — the worker-side
        tamper window.  Raises
        :class:`~repro.mpc.errors.AdversaryBudgetError` when more liars
        are detected than the budget tolerates.
        """
        from . import byzantine as byz

        stages = self.plan.stages()
        i_pts = stages.front(jnp.asarray(a, jnp.int64),
                             jnp.asarray(b, jnp.int64), key)
        tags = byz.share_tags(self.plan, i_pts, key)
        if injector is not None:
            i_pts, tags = injector.corrupt(self.plan, i_pts, tags, round_id)
        return self.verified_decode(i_pts, tags, key, survivors=survivors)

    def verified_decode(self, i_points, tags, key, *,
                        survivors: Optional[np.ndarray] = None):
        """Check share MACs, exclude liars, decode from honest survivors.

        Validates the mask at the verified quorum ``t²+z+2a`` (the ``2a``
        slack guarantees ``t²+z`` honest survivors for up to ``a`` liars),
        recomputes every alive slot's tag, and decodes through the plan's
        cached survivor tables exactly like a dropout mask — a detected
        liar and a crashed worker take the same decode path.  Returns
        ``(y, Verdict)`` with the liar slots for the eviction machinery.
        """
        from . import byzantine as byz
        from .errors import AdversaryBudgetError

        spec = self.spec
        budget = spec.adversaries
        n = self.n_workers
        spec.validate_survivors(survivors)       # shape + verified quorum
        alive = (np.ones(n, bool) if survivors is None
                 else np.asarray(survivors, bool))
        honest = byz.check_shares(self.plan, i_points, tags, key)
        liars = np.nonzero(alive & ~honest)[0]
        if len(liars) > budget:
            raise AdversaryBudgetError(
                f"adversary budget exhausted: {len(liars)} corrupted "
                f"shares detected > budget a={budget}",
                spec=spec, quorum=budget, alive=int(alive.sum()),
                slots=liars)
        idx = spec.validate_survivors(alive & honest, corrected=True)
        idx_j, rows_j = self.plan.survivor_tables(tuple(idx))
        y = self.plan.stages().decode(
            jnp.asarray(i_points, jnp.int64), idx_j, rows_j)
        return y, byz.Verdict(liars=tuple(int(w) for w in liars),
                              corrected=int(len(liars)),
                              quorum=tuple(int(i) for i in idx))

    def decode_corrected(self, i_points, *,
                         survivors: Optional[np.ndarray] = None,
                         max_errors: Optional[int] = None, seed: int = 0):
        """Tag-free error-correcting decode (Reed–Solomon/Berlekamp–Welch).

        The fallback when no MAC channel exists: compress each survivor's
        share matrix to one scalar with a seeded random vector (a wrong
        share maps to a wrong scalar except with probability ``1/p``),
        locate the corrupted evaluations with
        :func:`repro.mpc.byzantine.locate_errors` over the plan's α-set,
        and decode from the first ``t²+z`` clean survivors.  Consumes the
        same ``2a`` quorum slack as the verified path.  Returns
        ``(y, liar_slots)``.
        """
        from . import byzantine as byz

        budget = (self.spec.adversaries if max_errors is None
                  else int(max_errors))
        n = self.n_workers
        t2z = self.recovery_threshold
        p = self.field.p
        spec = self.spec if max_errors is None else dataclasses.replace(
            self.spec, adversaries=budget)
        spec.validate_survivors(survivors)       # shape + t²+z+2a quorum
        alive = (np.ones(n, bool) if survivors is None
                 else np.asarray(survivors, bool))
        aidx = np.nonzero(alive)[0]
        pts = np.asarray(jnp.asarray(i_points, jnp.int64)) % p
        flat = pts[aidx].reshape(len(aidx), -1)
        rng = np.random.default_rng(seed)
        from .lagrange import matmul_mod
        rvec = rng.integers(0, p, size=flat.shape[1], dtype=np.int64)
        comp = matmul_mod(flat, rvec.reshape(-1, 1), p)[:, 0]
        bad = byz.locate_errors(self.field, self.plan.alphas[aidx], comp,
                                t2z, budget)
        liars = aidx[bad]
        clean = alive.copy()
        clean[liars] = False
        idx = spec.validate_survivors(clean, corrected=True)
        idx_j, rows_j = self.plan.survivor_tables(tuple(int(i) for i in idx))
        y = self.plan.stages().decode(
            jnp.asarray(i_points, jnp.int64), idx_j, rows_j)
        return y, tuple(int(w) for w in liars)

    def run_reference(self, a, b, key, *,
                      survivors: Optional[np.ndarray] = None):
        """The pre-fast-path eager pipeline (oracle / benchmark baseline).

        Faithful to the seed implementation end to end, including its
        per-call phase-3 Vandermonde solve with the interpreted lagrange
        machinery — this is the baseline leg of the fused-vs-baseline pairs
        ``benchmarks/protocol_bench.py`` records.

        Exactness precondition: the eager einsums fold once after summing
        all ``ts+z`` terms (phase 1) / all ``N`` workers (phase 2), so the
        field window must cover those extents; guarded here instead of
        silently overflowing for small-window primes (Mersenne-31).
        """
        self._require_window("run_reference (mode='reference')")
        k1, k2 = jax.random.split(key)
        f_a, f_b = self.phase1_shares(a, b, k1)
        h = self.phase2_compute(f_a, f_b)
        i_pts = self.phase2_exchange(h, k2)
        return self._decode_seed(i_pts, survivors)

    def _decode_seed(self, i_points, survivors: Optional[np.ndarray] = None):
        """Seed-faithful decode: rebuilds and inverts the survivor system
        with the interpreted (object-dtype) lagrange implementations."""
        from .lagrange import inv_mod_ref, vandermonde_ref

        t2z = self.recovery_threshold
        alive = (np.ones(self.n_workers, bool) if survivors is None
                 else np.asarray(survivors, bool))
        idx = np.nonzero(alive)[0]
        if len(idx) < t2z:
            raise RuntimeError(
                f"only {len(idx)} workers alive < threshold {t2z}")
        idx = idx[:t2z]
        v = vandermonde_ref(self.field, self.alphas[idx], list(range(t2z)))
        w = inv_mod_ref(self.field, v)[: self.t * self.t]
        i_sel = jnp.asarray(i_points)[jnp.asarray(idx)]
        y_blocks = jnp.einsum("kn,nrc->krc", jnp.asarray(w), i_sel) % self.field.p
        t, mt = self.t, self.m // self.t
        grid = y_blocks.reshape(t, t, mt, mt)       # [l, i, r, c]
        return grid.transpose(1, 2, 0, 3).reshape(self.m, self.m)

    def _require_window(self, what: str) -> None:
        """Raise if the field's int64 window can't cover this path's
        single-fold accumulations (ts+z phase-1 terms, N exchange terms)."""
        need = max(self.s * self.t + self.z, self.n_workers)
        win = acc_window(self.field.p)
        if win < need:
            raise ValueError(
                f"{what} folds {need} products in one int64 window but "
                f"acc_window({self.field.p})={win}; use the default fused "
                "mode for small-window fields (DESIGN.md §3)")

    def _run_pallas(self, a, b, key, *,
                    survivors: Optional[np.ndarray] = None):
        """Phases 1-3 through the Pallas kernels (bit-exact with ``run``).

        Same window precondition as the reference path: the polyeval
        kernel keeps K fully resident with one fold at the end.  Survivor
        masks use the plan's cached decode tables, like the fused path.
        """
        _refuse_pallas_on_tpu()
        self._require_window("mode='pallas' (single-fold polyeval)")
        from ..kernels.polyeval import polyeval

        dec_idx = self.survivor_prefix(survivors)
        dec_rows = self.plan.survivor_rows(tuple(dec_idx))

        p = self.field.p
        t, z, m = self.t, self.z, self.m
        mt, ms = m // t, m // self.s
        n = self.n_workers
        k1, k2 = jax.random.split(key)
        ka, kb = jax.random.split(k1)
        sec_a = self.field.random(ka, (z, mt, ms))
        sec_b = self.field.random(kb, (z, ms, mt))
        terms_a = jnp.concatenate([self._split_a(a), sec_a]).reshape(-1, mt * ms)
        terms_b = jnp.concatenate([self._split_b(b), sec_b]).reshape(-1, ms * mt)
        f_a = polyeval(jnp.asarray(self.vand_a), terms_a, p=p).reshape(n, mt, ms)
        f_b = polyeval(jnp.asarray(self.vand_b), terms_b, p=p).reshape(n, ms, mt)
        h = self.phase2_compute(f_a, f_b, use_kernel=True)
        r_mask = self.field.random(k2, (n, z, mt, mt))
        i_pts = polyeval(jnp.asarray(self.g_mix.T.copy()),
                         h.reshape(n, mt * mt), p=p)
        mask_sum = mod_p(jnp.sum(r_mask, axis=0), p)
        i_pts = mod_p(
            i_pts + polyeval(jnp.asarray(self.vand_g_secret),
                             mask_sum.reshape(z, mt * mt), p=p), p)
        y_blocks = polyeval(jnp.asarray(dec_rows),
                            i_pts[jnp.asarray(dec_idx)],
                            p=p)
        grid = y_blocks.reshape(t, t, mt, mt)
        return grid.transpose(1, 2, 0, 3).reshape(m, m)

    # ------------------------------------------------------------- privacy
    def check_privacy_structure(self, n_subsets: int = 32, seed: int = 0) -> None:
        """The information-theoretic masking condition: for ANY ≤z colluding
        workers, the z×z secret-power Vandermonde submatrix is invertible
        (so the z uniform masks make shares uniform -- proof of [38] Thm 3).
        Exhaustive when the subset count is small, randomized otherwise."""
        from itertools import combinations

        sec_a = sorted(self.code.secret_powers_a)
        sec_b = sorted(self.code.secret_powers_b)
        combos = list(combinations(range(self.n_workers), self.z))
        if len(combos) > n_subsets:
            rng = np.random.default_rng(seed)
            sel = rng.choice(len(combos), n_subsets, replace=False)
            combos = [combos[i] for i in sel]
        for subset in combos:
            al = self.alphas[list(subset)]
            for pw in (sec_a, sec_b):
                v = vandermonde(self.field, al, pw)
                inv_mod(self.field, v)  # raises LinAlgError if singular


def expected_overheads(proto: AGECMPCProtocol) -> dict:
    """Cor. 8-10 evaluated for this protocol instance (scalar counts)."""
    from ..core.overheads import overheads

    o = overheads(proto.m, proto.s, proto.t, proto.z, proto.n_workers)
    return {
        "computation": o.computation,
        "storage": o.storage,
        "communication": o.communication,
    }
