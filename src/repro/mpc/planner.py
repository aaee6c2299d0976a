"""Cached protocol planning for AGE/Entangled/PolyDot-CMPC (DESIGN.md §2, §5).

A *plan* is everything about one ``Y = AᵀB`` protocol instance that does not
depend on the data: the degree-set code, the evaluation points α_n, the
reconstruction weights ``r_n^{(i,l)}`` (eq. (9)), the phase-1 Vandermonde
tables, the phase-2 G-mix matrix and the default phase-3 decode rows.
Building a plan costs one Vandermonde table + one Gauss–Jordan inverse per
α-set candidate — milliseconds with the vectorized :mod:`repro.mpc.lagrange`
machinery, but still far too much to redo on every ``run``/serve call under
heavy traffic.

:func:`get_plan` therefore memoizes plans process-wide, keyed by
``(scheme, s, t, z, lam, field.p, m)``.  Every
:class:`repro.mpc.protocol.AGECMPCProtocol` instance (and through it
``secure_matmul``, :class:`repro.mpc.elastic.ElasticPool`,
:class:`repro.mpc.engine.MPCEngine` and the benchmarks) resolves its tables
through this cache, so repeated protocol instances — e.g. one per serving
request — share alphas, ``r_coeffs``, Vandermonde tables *and* the
jit-compiled stage programs instead of recomputing them.  ``cache_info()`` /
``cache_clear()`` mirror ``functools.lru_cache`` semantics for tests and ops
introspection.

Beyond the static tables each plan owns (DESIGN.md §5):

* **staged jit programs** (:class:`ProtocolStages`, via :meth:`ProtocolPlan
  .stages`): ``encode`` / ``worker_compute`` / ``exchange`` / ``decode``,
  plus the compositions ``front`` (phases 1–2, survivor-mask independent)
  and ``fused`` (all three phases, default decode) — the decode stage takes
  the survivor index vector and decode rows as *traced arguments*, so one
  compiled program serves every survivor set;
* **a survivor-solve LRU** (:meth:`ProtocolPlan.survivor_rows`,
  :meth:`ProtocolPlan.quorum_weights`): phase-3 decode tables and phase-2
  pool-quorum reconstruction weights keyed by the frozen survivor index
  tuple, solved with the vectorized Montgomery/Gauss–Jordan path and
  evicted least-recently-used at :data:`SOLVE_CACHE_SIZE` entries;
* **spare evaluation points** (:meth:`ProtocolPlan.pool_alphas`): elastic
  pools extend the plan's invertibility-searched α-set instead of inventing
  their own, with the same deterministic re-seeding discipline.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.age import AGECode, GeneralizedPolyCode, optimal_age_code, polydot_code
from ..kernels.barrett import field_matmul, mod_p
from .errors import MaskShapeError
from .field import Field
from .lagrange import (
    ALPHA_POOL_LIMIT,
    ALPHA_SEARCH_SEED,
    ALPHA_SEARCH_TRIES,
    choose_alphas_with_inverse,
    inv_mod,
    inv_mod_ref,
    matmul_mod,
    power_table,
    try_inverse,
    vandermonde,
    vandermonde_ref,
)

# (scheme, s, t, z, lam, p, m) — plus, for heterogeneous-pool specs, one
# trailing evaluation-point placement tuple (DESIGN.md §8).  Placement
# permutes which physical device serves which worker slot; it never changes
# the tables or compiled programs, so a placement-qualified key ALIASES the
# placement-free plan in the cache (one build, one jit set) while keeping
# placement-distinct groups distinct in every plan_key-keyed map.
PlanKey = Tuple

# per-plan LRU capacity for survivor decode tables / quorum weights; each
# entry is a small int64 matrix (≤ N×N), so the cap bounds memory while
# keeping every straggler pattern a serving fleet realistically revisits hot
SOLVE_CACHE_SIZE = 128


def _powers_a(code: GeneralizedPolyCode) -> np.ndarray:
    """Coded power for each (i, j) block of Aᵀ, flattened i-major."""
    return np.array(
        [j * code.alpha + i * code.beta for i in range(code.t) for j in range(code.s)],
        dtype=np.int64,
    )


def _powers_b(code: GeneralizedPolyCode) -> np.ndarray:
    """Coded power for each (k, l) block of B, flattened k-major."""
    return np.array(
        [(code.s - 1 - k) * code.alpha + code.theta * l
         for k in range(code.s) for l in range(code.t)],
        dtype=np.int64,
    )


@dataclasses.dataclass(frozen=True)
class ProtocolStages:
    """Staged jit programs for one plan (DESIGN.md §5).

    The monolithic fused runner is split along the protocol's phase
    boundaries so elasticity and batching compose instead of falling back:

    * ``encode(a, b, k1) -> (f_a, f_b)`` — phase-1 shares for all N workers;
    * ``worker_compute(f_a, f_b) -> h`` — every worker's ``H(α_n)``;
    * ``exchange(h, k2) -> i_pts`` — G-mix + aggregate mask, ``[N, m/t, m/t]``;
    * ``decode(i_pts, idx, rows) -> y`` — phase 3; the survivor index vector
      and decode rows are *traced arguments*, so ONE compiled program serves
      every survivor set (the rows swap in from the plan's LRU);
    * ``front(a, b, key) -> i_pts`` — phases 1–2 in one program,
      survivor-mask independent (the batched engine vmaps this);
    * ``fused(a, b, key) -> y`` — all three phases with the default decode
      rows baked in (the no-dropout hot path, identical to the pre-split
      fused runner);
    * ``tags(i_pts, gamma, offsets, rvec) -> [N]`` — per-share field MAC
      tags ``γ·⟨vec(I(α_n)), r⟩ + o_n mod p`` for the Byzantine-verified
      path (DESIGN.md §9); MAC parameters are traced arguments, so one
      compiled program serves every request key.

    Every GEMM in them is :func:`repro.kernels.barrett.field_matmul` (int8
    limb dots with int32 accumulation), so every stage is bit-exact for
    any supported prime and lowers on TPU (DESIGN.md §3).
    """

    encode: Callable
    worker_compute: Callable
    exchange: Callable
    decode: Callable
    front: Callable
    fused: Callable
    tags: Callable

    def timed(self, recorder, *, plan: "ProtocolPlan" = None
              ) -> "ProtocolStages":
        """A copy whose stages time each *eager* call and feed the sink.

        ``recorder`` is duck-typed ``record(**kw)`` (e.g. :class:`repro
        .sim.trace.PhaseRecorder`); each call gets ``phase`` (the stage
        name), wall ``us`` (``block_until_ready``-fenced), ``scalars``
        (the stage's Cor. 8–10 work unit when ``plan`` is given, 0
        otherwise), ``device=-1`` and ``klass=<scheme>`` — a staged jit
        program runs all N logical workers at once, so samples are
        fleet-aggregate; per-device attribution comes from the simulator
        (DESIGN.md §11).

        The wrappers carry host-side timing fences: call them eagerly
        only.  Re-jitting or vmapping a timed stage would trace the
        fence into the program — keep handing the *raw* stages to
        ``plan.runner`` builders.
        """
        import time as _time

        counts = _stage_scalars(plan)
        klass = "stage" if plan is None else plan.scheme

        def wrap(name: str, fn: Callable) -> Callable:
            def timed_fn(*args, **kw):
                t0 = _time.perf_counter()
                out = jax.block_until_ready(fn(*args, **kw))
                recorder.record(
                    device=-1, klass=klass, phase=name,
                    scalars=counts.get(name, 0),
                    us=(_time.perf_counter() - t0) * 1e6, lanes=1)
                return out
            return timed_fn

        return ProtocolStages(**{
            name: wrap(name, getattr(self, name))
            for name in ("encode", "worker_compute", "exchange", "decode",
                         "front", "fused", "tags")})


def _stage_scalars(plan: Optional["ProtocolPlan"]) -> Dict[str, int]:
    """Per-stage scalar work units for one plan (the Cor. 8–10 counts the
    calibration layer normalizes measured wall time by): encode touches
    the 2N coded shares, worker_compute the N ξ-dominant block products,
    exchange the ζ all-pairs traffic, decode the quorum's ``(m/t)²``
    points; compositions sum their parts."""
    if plan is None:
        return {}
    n, s, t, z, m = (plan.n_workers, plan.s, plan.t, plan.z, plan.m)
    enc = 2 * n * (m * m) // (s * t)
    wc = int(n * m ** 3 / (s * t * t))
    exc = n * (n - 1) * m * m // (t * t)
    dec = (t * t + z) * (m // t) ** 2
    return {"encode": enc, "worker_compute": wc, "exchange": exc,
            "decode": dec, "front": enc + wc + exc,
            "fused": enc + wc + exc + dec, "tags": n * (m // t) ** 2}


def _build_stages(plan: "ProtocolPlan") -> ProtocolStages:
    """Compile the staged programs for one plan (DESIGN.md §3, §5).

    Bit-exactness matches the retired monolithic fused runner: phase-1
    secret draws replicate the reference path exactly; the phase-2 masks
    cancel identically in Y (``(V⁻¹V)[0:t², t²:t²+z] ≡ 0``), so the
    aggregate mask is drawn directly from raw bits mod p.  Every matmul
    is the int8 limb GEMM :func:`~repro.kernels.barrett.field_matmul`.
    Each stage body runs under the named scope ``mpc.<stage>``, so the
    device ops of ``front`` and ``fused`` carry their stage's name.
    """
    p, s, t, z, m = plan.p, plan.s, plan.t, plan.z, plan.m
    mt, ms = m // t, m // s
    n, t2z = plan.n_workers, plan.recovery_threshold

    def mm(x, y):
        return field_matmul(x, y, p=p)

    va = jnp.asarray(plan.vand_a)
    vb = jnp.asarray(plan.vand_b)
    gm_t = jnp.asarray(plan.g_mix.T.copy())       # [n', n]
    vg = jnp.asarray(plan.vand_g_secret)          # [n', z]
    dec = jnp.asarray(plan.decode_rows)           # [t², t²+z]
    default_idx = jnp.arange(t2z)

    def encode(a, b, k1):
        with jax.named_scope("mpc.encode"):
            ka, kb = jax.random.split(k1)
            sec_a = jax.random.randint(ka, (z, mt, ms), 0, p, dtype=jnp.int64)
            sec_b = jax.random.randint(kb, (z, ms, mt), 0, p, dtype=jnp.int64)
            at = a.T.reshape(t, mt, s, ms).transpose(0, 2, 1, 3)
            blocks_a = at.reshape(t * s, mt, ms)
            blocks_b = b.reshape(s, ms, t, mt).transpose(0, 2, 1, 3).reshape(
                s * t, ms, mt)
            terms_a = jnp.concatenate([blocks_a, sec_a]).reshape(-1, mt * ms)
            terms_b = jnp.concatenate([blocks_b, sec_b]).reshape(-1, ms * mt)
            f_a = mm(va, terms_a).reshape(n, mt, ms)
            f_b = mm(vb, terms_b).reshape(n, ms, mt)
            return f_a, f_b

    def worker_compute(f_a, f_b):
        with jax.named_scope("mpc.worker_compute"):
            return mm(f_a, f_b)                               # [n, mt, mt]

    def exchange(h, k2):
        with jax.named_scope("mpc.exchange"):
            mask_sum = (jax.random.bits(k2, (z, mt, mt), jnp.uint64)
                        % jnp.uint64(p)).astype(jnp.int64)
            i_pts = mm(gm_t, h.reshape(n, mt * mt))
            i_pts = mod_p(i_pts + mm(vg, mask_sum.reshape(z, mt * mt)), p)
            return i_pts.reshape(n, mt, mt)

    def decode(i_pts, idx, rows):
        with jax.named_scope("mpc.decode"):
            i_sel = jnp.take(jnp.asarray(i_pts, jnp.int64), idx, axis=0)
            y_blocks = mm(jnp.asarray(rows, jnp.int64),
                          i_sel.reshape(t2z, mt * mt))
            grid = y_blocks.reshape(t, t, mt, mt)             # [l, i, r, c]
            return grid.transpose(1, 2, 0, 3).reshape(m, m)

    def front(a, b, key):
        k1, k2 = jax.random.split(key)
        return exchange(worker_compute(*encode(a, b, k1)), k2)

    def fused(a, b, key):
        return decode(front(a, b, key), default_idx, dec)

    def tags(i_pts, gamma, offsets, rvec):
        # γ·⟨vec(I(α_n)), r⟩ + o_n mod p (DESIGN.md §9).  The compression
        # dot is the shared field GEMM (K-chunked to its window); the final
        # γ·v + o fits int64 for any p < 2³¹·⁵: v, γ < p ⇒ γ·v < 2⁶².
        with jax.named_scope("mpc.tags"):
            v = mm(jnp.asarray(i_pts, jnp.int64).reshape(n, mt * mt),
                   rvec.reshape(mt * mt, 1))[:, 0]
            return (gamma * v + offsets) % p

    return ProtocolStages(
        encode=jax.jit(encode), worker_compute=jax.jit(worker_compute),
        exchange=jax.jit(exchange), decode=jax.jit(decode),
        front=jax.jit(front), fused=jax.jit(fused), tags=jax.jit(tags))


@dataclasses.dataclass(eq=False)  # identity semantics (ndarray fields;
class ProtocolPlan:               # the cache's contract is `is`, not `==`)
    """Data-independent tables for one protocol instance (all int64 numpy)."""

    scheme: str
    s: int
    t: int
    z: int
    m: int
    p: int
    code: GeneralizedPolyCode
    alphas: np.ndarray          # [N] evaluation points
    powers_h: np.ndarray        # [N] sorted support of H(x)
    r_coeffs: np.ndarray        # [t², N]  eq. (9) rows, u = i + t·l
    vand_a: np.ndarray          # [N, ts+z] phase-1 F_A table
    vand_b: np.ndarray          # [N, ts+z] phase-1 F_B table
    g_mix: np.ndarray           # [N, N']  phase-2 H→G mixing scalars
    vand_g_secret: np.ndarray   # [N, z]   phase-2 mask table
    decode_rows: np.ndarray     # [t², t²+z] default (all-alive) decode rows

    # lazily-attached compiled runners, keyed by backend name — shared by
    # every protocol instance that resolves to this plan
    _runners: Dict[str, Callable] = dataclasses.field(
        default_factory=dict, repr=False)
    _runner_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)
    # survivor-solve LRU (phase-3 decode tables + phase-2 quorum weights),
    # keyed by the frozen survivor index tuple — DESIGN.md §5
    _solve_cache: "OrderedDict" = dataclasses.field(
        default_factory=OrderedDict, repr=False)
    _solve_hits: int = dataclasses.field(default=0, repr=False)
    _solve_misses: int = dataclasses.field(default=0, repr=False)
    # provisioned pool α-sets, keyed by pool size (elastic layer)
    _pool_alphas: Dict[int, np.ndarray] = dataclasses.field(
        default_factory=dict, repr=False)
    _field: Optional[Field] = dataclasses.field(default=None, repr=False)

    @property
    def n_workers(self) -> int:
        return len(self.alphas)

    @property
    def recovery_threshold(self) -> int:
        return self.t * self.t + self.z

    @property
    def field(self) -> Field:
        """A ``Field`` over this plan's prime (modular solves only — the
        fixed-point ``frac_bits`` is irrelevant here and left at default)."""
        f = self._field
        if f is None:
            f = self._field = Field(self.p)
        return f

    def runner(self, kind: str, build: Callable[[], Callable]) -> Callable:
        """Get-or-build a compiled runner attached to this plan.

        Locked so concurrent first-callers (one protocol instance per
        serving request) pay the jit compile once, like the plan cache."""
        fn = self._runners.get(kind)
        if fn is None:
            with self._runner_lock:
                fn = self._runners.get(kind)
                if fn is None:
                    fn = self._runners[kind] = build()
        return fn

    def stages(self) -> ProtocolStages:
        """The staged jit programs for this plan (compiled once, shared)."""
        return self.runner("stages", lambda: _build_stages(self))

    # ------------------------------------------------- survivor-solve cache
    def _solve_cached(self, key: Tuple, solve: Callable[[], np.ndarray]
                      ) -> np.ndarray:
        """LRU get-or-solve: recently-used survivor patterns stay hot; the
        cache evicts least-recently-used past SOLVE_CACHE_SIZE entries."""
        with self._runner_lock:
            val = self._solve_cache.get(key)
            if val is not None:
                self._solve_cache.move_to_end(key)
                self._solve_hits += 1
                return val
        val = solve()
        with self._runner_lock:
            hit = self._solve_cache.get(key)
            if hit is not None:  # benign solve race: keep the first
                self._solve_cache.move_to_end(key)
                self._solve_hits += 1
                return hit
            self._solve_misses += 1
            self._solve_cache[key] = val
            while len(self._solve_cache) > SOLVE_CACHE_SIZE:
                self._solve_cache.popitem(last=False)
        return val

    def survivor_rows(self, idx) -> np.ndarray:
        """Phase-3 decode rows ``[t², t²+z]`` for one survivor index tuple.

        ``idx``: the first ``t²+z`` alive worker indices, ascending.  The
        default prefix short-circuits to :attr:`decode_rows` (so an
        explicitly-passed all-True mask costs nothing); any other pattern
        hits the LRU, solved on miss with the vectorized Montgomery/
        Gauss–Jordan path (never the ``*_ref`` oracles).
        """
        t2z = self.recovery_threshold
        idx = tuple(int(i) for i in idx)
        if len(idx) != t2z:
            raise MaskShapeError(
                f"need exactly {t2z} survivor indices, got {len(idx)}",
                quorum=t2z, alive=len(idx), slots=idx)
        if idx == tuple(range(t2z)):
            return self.decode_rows

        def solve() -> np.ndarray:
            v = vandermonde(self.field, self.alphas[list(idx)],
                            np.arange(t2z, dtype=np.int64))
            return inv_mod(self.field, v)[: self.t * self.t]

        return self._solve_cached(("survivor", idx), solve)

    def survivor_tables(self, idx) -> Tuple:
        """Device-resident ``(indices, decode rows)`` for one survivor tuple.

        The jnp twins of :meth:`survivor_rows`, LRU-cached alongside them so
        repeat decodes of a known straggler pattern skip the host→device
        transfer entirely — the serving hot path feeds these straight into
        the compiled decode stage.
        """
        idx = tuple(int(i) for i in idx)

        def build() -> Tuple:
            rows = self.survivor_rows(idx)
            return (jnp.asarray(np.asarray(idx, np.int64)),
                    jnp.asarray(rows))

        return self._solve_cached(("survivor_dev", idx), build)

    def quorum_weights(self, idx, pool_size: int) -> np.ndarray:
        """Phase-2 reconstruction weights (inverse of the generalized
        Vandermonde over ``P(H)``, eq. (9)) for an elastic-pool quorum.

        ``idx``: N worker indices into the ``pool_size`` provisioned pool
        (:meth:`pool_alphas`).  LRU-cached like :meth:`survivor_rows`.
        """
        n = self.n_workers
        idx = tuple(int(i) for i in idx)
        if len(idx) != n:
            raise MaskShapeError(
                f"need exactly N={n} quorum indices, got {len(idx)}",
                quorum=n, alive=len(idx), slots=idx)

        def solve() -> np.ndarray:
            al = self.pool_alphas(pool_size)[list(idx)]
            v = vandermonde(self.field, al, self.powers_h)
            return inv_mod(self.field, v)

        return self._solve_cached(("quorum", pool_size, idx), solve)

    def solve_cache_info(self) -> Dict[str, int]:
        with self._runner_lock:
            return {"hits": self._solve_hits, "misses": self._solve_misses,
                    "size": len(self._solve_cache)}

    # --------------------------------------------------- spare α provisioning
    def pool_alphas(self, pool_size: int) -> np.ndarray:
        """Evaluation points for an elastic pool of ``pool_size ≥ N`` workers.

        The first N entries are exactly this plan's (invertibility-searched,
        possibly re-seeded) α's — shares distributed in phase 1 and spare
        points live on ONE polynomial evaluation grid.  Spares extend the
        set with the smallest unused field points, each validated with the
        same re-seeding discipline as the base search: appending spare k
        must keep the canonical prefix-failure quorum (pool workers
        ``k−N+1 … k``) solvable over ``P(H)``; singular candidates are
        skipped deterministically.  Results are memoized per pool size.
        """
        n = self.n_workers
        if pool_size < n:
            raise ValueError(f"pool_size {pool_size} < N={n}")
        if pool_size >= self.p:
            raise ValueError(
                f"pool_size {pool_size} needs distinct nonzero α's mod "
                f"{self.p}")
        with self._runner_lock:
            cached = self._pool_alphas.get(pool_size)
        if cached is not None:
            return cached
        pool = [int(a) for a in self.alphas]
        used = {a % self.p for a in pool}
        rng = np.random.default_rng(ALPHA_SEARCH_SEED)
        fresh = (a for a in range(1, min(self.p, ALPHA_POOL_LIMIT))
                 if a not in used)
        while len(pool) < pool_size:
            for _ in range(ALPHA_SEARCH_TRIES):
                cand = next(fresh, None)
                if cand is None:  # tiny fields: re-seeded random fallback
                    cand = int(rng.integers(1, self.p))
                    if cand in used:
                        continue
                quorum = np.array(pool[len(pool) - n + 1:] + [cand], np.int64)
                if try_inverse(self.field,
                               vandermonde(self.field, quorum,
                                           self.powers_h)) is not None:
                    pool.append(cand)
                    used.add(cand % self.p)
                    break
            else:
                raise RuntimeError(
                    f"no invertible spare α found in {ALPHA_SEARCH_TRIES} "
                    f"tries extending pool to {len(pool) + 1}")
        arr = np.array(pool, dtype=np.int64)
        with self._runner_lock:
            arr = self._pool_alphas.setdefault(pool_size, arr)
        return arr


@functools.lru_cache(maxsize=None)
def _resolve_code(scheme: str, s: int, t: int, z: int,
                  lam: Optional[int]) -> GeneralizedPolyCode:
    if scheme == "age":
        if lam is None:
            return optimal_age_code(s, t, z)[0]
        return AGECode(s, t, z, lam)
    if scheme == "entangled":
        return AGECode(s, t, z, lam=0)
    if scheme == "polydot":
        return polydot_code(s, t, z)
    raise ValueError(f"unknown scheme {scheme!r}")


def build_plan(scheme: str, s: int, t: int, z: int, lam: Optional[int],
               field: Field, m: int, *, use_reference: bool = False) -> ProtocolPlan:
    """Construct a plan from scratch (no cache).

    ``use_reference=True`` rebuilds with the original interpreted lagrange
    implementations (object-dtype Gauss–Jordan, per-element ``pow``
    Vandermonde, and the seed's separate invert-to-check + invert-to-solve
    structure).  It exists as the bit-exactness oracle and the baseline leg
    of the plan-construction speedup pair in ``benchmarks/protocol_bench.py``.
    """
    code = _resolve_code(scheme, s, t, z, lam)
    p = field.p
    n = code.n_workers
    powers_h = np.array(sorted(code.powers_h), dtype=np.int64)
    t2 = t * t
    t2z = t2 + z
    pw_a = np.concatenate(
        [_powers_a(code), np.array(sorted(code.secret_powers_a), np.int64)])
    pw_b = np.concatenate(
        [_powers_b(code), np.array(sorted(code.secret_powers_b), np.int64)])
    max_pow = int(max(powers_h.max(), pw_a.max(), pw_b.max(), t2z - 1))

    # ---- α-set search: invertibility check and solve share one elimination
    table = None
    if use_reference:
        # seed structure: check-invert, then re-build + solve-invert (the
        # honest baseline cost), over the same shared search constants
        rng = np.random.default_rng(ALPHA_SEARCH_SEED)
        alphas = np.arange(1, n + 1, dtype=np.int64)
        w = None
        for _ in range(ALPHA_SEARCH_TRIES):
            try:
                inv_mod_ref(field, vandermonde_ref(field, alphas, powers_h))
                w = inv_mod_ref(field, vandermonde_ref(field, alphas, powers_h))
                break
            except np.linalg.LinAlgError:
                alphas = rng.choice(
                    np.arange(1, min(p, ALPHA_POOL_LIMIT), dtype=np.int64),
                    size=n, replace=False)
        if w is None:
            raise RuntimeError(
                f"no invertible α-set found in {ALPHA_SEARCH_TRIES} tries")
    else:
        holder = {}

        def _table_slice(f, cand, pw):
            holder["table"] = tbl = power_table(f, cand, max_pow)
            return tbl[:, np.asarray(pw, np.int64)]

        alphas, w = choose_alphas_with_inverse(
            field, n, powers_h, vand_fn=_table_slice)
        table = holder["table"]

    def vand(al_rows, pw):
        """α^pw table: a column slice of the shared power table (fast path)
        or a fresh per-element build (reference path).  ``al_rows`` is a
        row count into ``alphas`` (prefix) to keep slicing trivial."""
        if use_reference:
            return vandermonde_ref(field, alphas[:al_rows], pw)
        return table[:al_rows, np.asarray(pw, np.int64)]

    # ---- r_coeffs: rows of V⁻¹ at the important powers, ordered u = i + t·l
    pow_to_idx = {int(pw): k for k, pw in enumerate(powers_h)}
    rows = [
        w[pow_to_idx[(code.s - 1) * code.alpha + i * code.beta + code.theta * l]]
        for l in range(t) for i in range(t)
    ]
    r_coeffs = np.stack(rows).astype(np.int64)

    # ---- phase-1 share tables (coded powers then secret powers)
    vand_a = vand(n, pw_a)
    vand_b = vand(n, pw_b)

    # ---- phase-2 G-mix: c[n, n'] = Σ_u r_n^u · α_{n'}^u  (eq. (10), 1st sum)
    vg = vand(n, np.arange(t2, dtype=np.int64))                 # [N', t²]
    if use_reference:
        g_mix = ((r_coeffs.astype(object).T @ vg.astype(object).T)
                 % p).astype(np.int64)
    else:
        g_mix = matmul_mod(r_coeffs.T, vg.T, p)                  # [N, N']
    vand_g_secret = vand(n, np.array([t2 + w_ for w_ in range(z)], np.int64))

    # ---- default phase-3 decode: first t²+z workers, coefficients 0..t²-1
    v_dec = vand(t2z, np.arange(t2z, dtype=np.int64))
    if use_reference:
        decode_rows = inv_mod_ref(field, v_dec)[:t2]
    else:
        w_dec = try_inverse(field, v_dec)
        if w_dec is None:  # cannot happen: plain Vandermonde, distinct α's
            raise np.linalg.LinAlgError("singular decode system")
        decode_rows = w_dec[:t2]

    return ProtocolPlan(
        scheme=scheme, s=s, t=t, z=z, m=m, p=p, code=code,
        alphas=alphas, powers_h=powers_h, r_coeffs=r_coeffs,
        vand_a=vand_a, vand_b=vand_b, g_mix=g_mix,
        vand_g_secret=vand_g_secret, decode_rows=decode_rows.astype(np.int64),
    )


# ----------------------------------------------------------------- the cache
_CACHE: Dict[PlanKey, ProtocolPlan] = {}
_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0


def get_plan(scheme: str, s: int, t: int, z: int, lam: Optional[int],
             field: Field, m: int, *,
             placement: Optional[Tuple[int, ...]] = None) -> ProtocolPlan:
    """Memoized :func:`build_plan` — the entry point protocols use.

    ``placement`` (heterogeneous pools, DESIGN.md §8) qualifies the cache
    key without changing what is built: the returned plan IS the
    placement-free plan object (tables and compiled stages are
    placement-independent), registered under the qualified key so
    ``plan_key``-keyed maps keep placement-distinct groups apart.
    """
    global _HITS, _MISSES
    key: PlanKey = (scheme, s, t, z, lam, field.p, m)
    if placement is not None:
        key = key + (tuple(int(d) for d in placement),)
    with _LOCK:
        plan = _CACHE.get(key)
        if plan is not None:
            _HITS += 1
            return plan
    if placement is None:
        built = build_plan(scheme, s, t, z, lam, field, m)
    else:  # alias the shared placement-free plan (one build, one jit set)
        built = get_plan(scheme, s, t, z, lam, field, m)
    with _LOCK:
        plan = _CACHE.get(key)
        if plan is not None:  # lost a benign build race: keep the first
            _HITS += 1
            return plan
        _MISSES += 1
        _CACHE[key] = built
    return built


def cache_info() -> Dict[str, int]:
    with _LOCK:
        return {"hits": _HITS, "misses": _MISSES, "size": len(_CACHE)}


def cache_clear() -> None:
    global _HITS, _MISSES
    with _LOCK:
        _CACHE.clear()
        _HITS = 0
        _MISSES = 0
