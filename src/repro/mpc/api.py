"""The unified MPC surface: ``MPCSpec`` + ``MPCSession`` (DESIGN.md §6).

One frozen, validated **spec** replaces the ``(s, t, z, m, lam, scheme,
field)`` kwarg blobs that ``protocol.py``, ``engine.py``, ``elastic.py``
and ``secure_matmul.py`` each re-took, and one **session** exposes a single
verb set over three pluggable backends:

    spec = MPCSpec(s=2, t=2, z=2)
    sess = connect(spec)                      # local | sharded | batched
    y = sess.matmul(a, b)                     # floats in, floats out

* :class:`MPCSpec` — scheme, partitioning, collusion bound, gap, field and
  fixed-point encoding config in one hashable object.  It is the single
  source of truth for plan keys (:meth:`MPCSpec.plan_key`), plan resolution
  (:meth:`MPCSpec.plan`), protocol construction (:meth:`MPCSpec.protocol`)
  and survivor-mask validation (:meth:`MPCSpec.validate_survivors` — the
  public form of what used to be ``AGECMPCProtocol._survivor_prefix``).
* :class:`MPCSession` — ``matmul(a, b)``, ``submit``/``flush``,
  ``fail(workers)``, ``validate_survivors(mask)``.  Operands may be
  rectangular ``[r,k]×[k,c]`` and carry leading batch dimensions; the
  shape adapter (:mod:`repro.mpc.tiling`) maps them onto the coded ``m×m``
  block grid, the backend executes the blocks, and the session folds field
  encode/decode in so callers pass floats end to end.
* backends (:mod:`repro.mpc.backends`) — ``local`` (the fused / pallas /
  reference staged-jit paths), ``sharded`` (the mesh/``psum_scatter``
  runner) and ``batched`` (the ``MPCEngine`` grouping/vmap machinery; a
  tiled call becomes ONE engine flush).

Key discipline: a call that maps to a single coded block consumes the
caller's key directly — bit-identical to ``AGECMPCProtocol.run`` — while a
multi-block call folds a per-block counter into the base key so every
block draws distinct phase-1/2 randomness.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import span
from .errors import MaskShapeError, QuorumError
from .field import DEFAULT_FIELD, Field
from .planner import PlanKey, ProtocolPlan, _resolve_code, get_plan
from .tiling import (
    DEFAULT_TILE_BUDGET,
    TileMap,
    assemble,
    choose_block,
    choose_block_cost,
    tile_blocks,
)
from .workers import WorkerPool

SCHEMES = ("age", "entangled", "polydot")


# ===================================================================== spec
@dataclasses.dataclass(frozen=True)
class MPCSpec:
    """Frozen, validated protocol parameterization.

    Parameters
    ----------
    s, t : matrix partitions (the paper's s×t block grid)
    z    : collusion bound
    lam  : AGE gap; ``None`` solves ``min_λ`` (eq. (13))
    scheme : "age" | "entangled" | "polydot"
    field  : prime field + fixed-point encoding config (``Field.frac_bits``)
    m      : optional default protocol block side (``s|m`` and ``t|m``).
             When unset, the session's shape adapter picks a block size per
             workload (:func:`repro.mpc.tiling.choose_block`).
    pool   : optional heterogeneous device roster
             (:class:`repro.mpc.workers.WorkerPool`, DESIGN.md §8).  With a
             pool, worker ids seen by :meth:`MPCSession.fail` /
             :meth:`MPCEngine.fail` are roster *device* ids and are
             translated to protocol slots through the placement; survivor
             masks stay slot-indexed (``[N]`` bools).
    placement : optional evaluation-point placement — the roster device id
             serving each protocol slot ``0..N-1`` (distinct, in range).
             ``None`` with a pool means the identity prefix (device ``n``
             serves slot ``n`` — the capacity-oblivious default; the tuner
             bakes in an optimized one).
    adversaries : Byzantine budget ``a`` ≥ 0 (DESIGN.md §9): how many
             workers may return *wrong* shares per round (not merely
             vanish).  ``a > 0`` raises the serving quorum to the
             verified threshold ``t²+z + 2a`` and routes every decode
             through MAC verification (liars are localized, excluded and
             evicted through the ``fail``/``retune`` path).  The code's
             worker count must cover the verified threshold.
    """

    s: int
    t: int
    z: int
    lam: Optional[int] = None
    scheme: str = "age"
    field: Field = DEFAULT_FIELD
    m: Optional[int] = None
    pool: Optional[WorkerPool] = None
    placement: Optional[Tuple[int, ...]] = None
    adversaries: int = 0

    def __post_init__(self):
        if self.scheme not in SCHEMES:
            raise ValueError(
                f"unknown scheme {self.scheme!r}: expected one of {SCHEMES}")
        for name in ("s", "t", "z"):
            v = getattr(self, name)
            if not isinstance(v, (int, np.integer)) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        if self.lam is not None and self.lam < 0:
            raise ValueError(f"lam must be None or >= 0, got {self.lam!r}")
        if not isinstance(self.field, Field):
            raise TypeError(f"field must be a Field, got {self.field!r}")
        if self.m is not None and (self.m < 1 or self.m % self.s
                                   or self.m % self.t):
            raise ValueError(
                f"need s|m and t|m: s={self.s} t={self.t} m={self.m}")
        if self.pool is not None and not isinstance(self.pool, WorkerPool):
            raise TypeError(f"pool must be a WorkerPool, got {self.pool!r}")
        if self.placement is not None:
            if self.pool is None:
                raise ValueError("placement requires a pool")
            pl = tuple(int(d) for d in self.placement)
            if len(set(pl)) != len(pl) or any(
                    not 0 <= d < len(self.pool) for d in pl):
                raise ValueError(
                    f"placement must be distinct device ids within the "
                    f"{len(self.pool)}-device pool, got {self.placement!r}")
            object.__setattr__(self, "placement", pl)
        a = self.adversaries
        if isinstance(a, bool) or not isinstance(a, (int, np.integer)) or a < 0:
            raise ValueError(
                f"adversaries must be an int >= 0, got {a!r}")
        if a > 0 and self.n_workers < self.verified_threshold:
            raise ValueError(
                f"adversary budget a={a} needs N >= t²+z+2a = "
                f"{self.verified_threshold} workers but the "
                f"{self.scheme} code provides only N={self.n_workers}")

    # ------------------------------------------------------------ identity
    def replace(self, **kw) -> "MPCSpec":
        """A copy with the given fields replaced (validated again)."""
        return dataclasses.replace(self, **kw)

    def plan_key(self, m: Optional[int] = None) -> PlanKey:
        """The process-wide planner-cache key for this spec (+ block side).

        Pool-free specs keep the legacy 7-tuple; a pool appends the
        effective placement (the permutation never changes the plan's
        tables — the qualified key aliases the shared plan — but keeps
        placement-distinct groups apart in plan_key-keyed maps)."""
        base = (self.scheme, self.s, self.t, self.z, self.lam,
                self.field.p, self._block(m))
        if self.pool is None:
            return base
        return base + (self.effective_placement,)

    @property
    def pool_key(self) -> Optional[Tuple]:
        """Hashable roster signature, or ``None`` without a pool."""
        return None if self.pool is None else self.pool.key

    def group_key(self, m: Optional[int] = None) -> Tuple:
        """Serving-group identity: ``plan_key`` alone for pool-free specs
        (legacy-compatible), extended with the pool signature otherwise —
        the ``(plan_key, pool_key)`` grouping the batched engine uses.
        A nonzero adversary budget is part of the identity too (verified
        and unverified requests must never share one serving group), but
        ``a = 0`` keeps the legacy key bit-for-bit."""
        pk = self.plan_key(m)
        if self.pool is not None:
            pk = pk + (self.pool.key,)
        if self.adversaries:
            pk = pk + (("byz", self.adversaries),)
        return pk

    @property
    def effective_placement(self) -> Optional[Tuple[int, ...]]:
        """The placement actually in force: ``None`` without a pool, the
        explicit placement when set (validated against N), else the
        identity prefix — device ``n`` serves slot ``n``."""
        if self.pool is None:
            return None
        n = self.n_workers
        if self.placement is not None:
            if len(self.placement) != n:
                raise ValueError(
                    f"placement has {len(self.placement)} devices but the "
                    f"code needs N={n} workers")
            return self.placement
        if len(self.pool) < n:
            raise ValueError(
                f"pool has {len(self.pool)} devices < N={n}")
        return tuple(range(n))

    def slots_for(self, devices) -> Tuple[int, ...]:
        """Translate worker ids to protocol slots for this spec.

        Without a pool, ids already ARE slots (legacy semantics).  With a
        pool, ids are roster device ids; devices outside the placement
        (spares, bystanders) have no slot and are dropped — the elastic
        layer tracks those separately."""
        pl = self.effective_placement
        if pl is None:
            return tuple(sorted(int(d) for d in devices))
        inv = {d: i for i, d in enumerate(pl)}
        return tuple(sorted(inv[int(d)] for d in devices if int(d) in inv))

    def _block(self, m: Optional[int]) -> int:
        m = self.m if m is None else m
        if m is None:
            raise ValueError(
                "no block size: pass m or construct the spec with one")
        return int(m)

    # ------------------------------------------------------- derived facts
    @property
    def code(self):
        """The degree-set code (memoized; independent of the block side)."""
        return _resolve_code(self.scheme, self.s, self.t, self.z, self.lam)

    @property
    def n_workers(self) -> int:
        return self.code.n_workers

    @property
    def recovery_threshold(self) -> int:
        return self.t * self.t + self.z

    @property
    def verified_threshold(self) -> int:
        """Alive workers a Byzantine-verified decode needs: ``t²+z + 2a``.

        The ``2a`` slack covers both defenses uniformly (DESIGN.md §9):
        the MAC path needs ``t²+z`` *honest* survivors (≥ a liars to
        spare), and the tag-free Berlekamp–Welch path consumes the same
        ``2a`` extra points as error-locator equations.  Equals the plain
        recovery threshold when ``a = 0``.
        """
        return self.recovery_threshold + 2 * self.adversaries

    @property
    def frac_bits(self) -> int:
        return self.field.frac_bits

    # ----------------------------------------------------------- factories
    @classmethod
    def tune(cls, n_workers: Optional[int] = None, z: int = None,
             shape=None, **kw) -> "MPCSpec":
        """Autotuned spec for a worker budget + workload (DESIGN.md §7).

        Solves the paper's optimization layer: search AGE over every
        feasible ``(s, t, λ)`` (plus Entangled and PolyDot) under the
        closed-form/enumerated worker counts, rank by the weighted
        Cor. 8–10 overhead objective (``cost=CostModel(...)``), and
        co-optimize the coded tile side ``m`` jointly with ``(s, t)``
        against ``shape = (r, k, c)`` (+ ``batch``).  Returns the winning
        frozen spec with its block side baked in —
        ``connect(MPCSpec.tune(N, z, shape))`` is the one-liner.  Use
        :func:`repro.mpc.autotune.tune` directly for the full ranked
        candidate list and the tuned tile budget.  ``pool=`` (a
        :class:`repro.mpc.workers.WorkerPool`) switches the objective to
        the per-worker-weighted form and bakes the co-optimized
        evaluation-point placement into the returned spec (DESIGN.md §8).
        """
        from .autotune import tune as _tune

        return _tune(n_workers, z, shape, **kw).spec

    def plan(self, m: Optional[int] = None) -> ProtocolPlan:
        """The cached data-independent tables for this spec at block ``m``."""
        return get_plan(self.scheme, self.s, self.t, self.z, self.lam,
                        self.field, self._block(m),
                        placement=self.effective_placement)

    def protocol(self, m: Optional[int] = None):
        """An :class:`~repro.mpc.protocol.AGECMPCProtocol` for block ``m``."""
        from .protocol import AGECMPCProtocol

        return AGECMPCProtocol.from_spec(self, m=m)

    # ------------------------------------------------- survivor validation
    def validate_survivors(self, survivors, *,
                           corrected: bool = False) -> np.ndarray:
        """First ``t²+z`` alive worker indices for a survivor mask.

        The public survivor-mask contract (formerly the protocol-private
        ``_survivor_prefix``), raising from the structured taxonomy of
        :mod:`repro.mpc.errors`: :class:`~repro.mpc.errors.MaskShapeError`
        (a ``ValueError``) on a mis-shaped mask, and
        :class:`~repro.mpc.errors.QuorumError` (a ``RuntimeError``) when
        fewer workers survive than the quorum — ``t²+z`` for plain specs,
        the verified threshold ``t²+z + 2a`` when ``adversaries > 0``
        (the ``2a`` slack funds liar detection; DESIGN.md §9).  Pass
        ``corrected=True`` for a mask that has *already* been through MAC
        verification (liars excluded): only the plain ``t²+z`` decode
        quorum applies then.  The returned prefix is always the ``t²+z``
        decode quorum; its frozen tuple keys the plan's survivor-table
        LRU.
        """
        t2z = self.recovery_threshold
        need = t2z if corrected else self.verified_threshold
        n = self.n_workers
        alive = (np.ones(n, bool) if survivors is None
                 else np.asarray(survivors, bool))
        if alive.shape != (n,):
            raise MaskShapeError(
                f"survivors mask must have shape ({n},), got {alive.shape}",
                spec=self, quorum=need)
        idx = np.nonzero(alive)[0]
        if len(idx) < need:
            detail = ("" if need == t2z else
                      f" (verified quorum t²+z+2a for adversary budget "
                      f"a={self.adversaries})")
            raise QuorumError(
                f"only {len(idx)} workers alive < threshold {need}{detail}",
                spec=self, quorum=need, alive=len(idx),
                slots=np.nonzero(~alive)[0])
        return idx[:t2z]


# ================================================================== blocks
@dataclasses.dataclass(frozen=True)
class BlockOp:
    """One coded ``m×m`` block product ``Y = AᵀB`` for a backend to run."""

    proto: Any                       # AGECMPCProtocol
    a: jnp.ndarray                   # [m, m] field elements (the Aᵀ operand)
    b: jnp.ndarray                   # [m, m] field elements
    key: jnp.ndarray
    survivors: Optional[np.ndarray]  # bool [N] or None


@dataclasses.dataclass(frozen=True)
class BlockFailure:
    """A block a backend could not serve (below threshold, infeasible)."""

    reason: str


@dataclasses.dataclass
class _Request:
    """One logical session matmul: its block ops + how to reassemble.

    ``raw`` keeps the un-tiled call (operands, key, flags + the logical
    ``shape``/``batch``) so a queued request can be re-tiled when an
    attrition drain adopts a spec with a different block side
    (DESIGN.md §8); ``None`` for degenerate zero-size requests.
    """

    rid: int
    ops: List[BlockOp]
    build: Callable[[List[jnp.ndarray]], jnp.ndarray]
    raw: Optional[Dict[str, Any]] = None


# ================================================================= session
class MPCSession:
    """One verb set over a pluggable backend (obtain via :func:`connect`).

    * :meth:`matmul` — rectangular/batched float (or field) matmul;
    * :meth:`submit` / :meth:`flush` — queue many matmuls, serve together
      (on the batched backend a whole flush is ONE engine flush);
    * :meth:`fail` — report worker attrition (folded into later decodes;
      the batched backend escalates through its elastic pools);
    * :meth:`validate_survivors` — the spec's public mask validation.
    """

    def __init__(self, spec: MPCSpec, backend, *, key=None,
                 tile_budget: int = DEFAULT_TILE_BUDGET, cost=None):
        if not isinstance(spec, MPCSpec):
            raise TypeError(f"spec must be an MPCSpec, got {spec!r}")
        # fail fast at session construction, not at first matmul: a bad
        # dispatch budget used to surface only inside choose_block once
        # real traffic arrived
        if (isinstance(tile_budget, bool)
                or not isinstance(tile_budget, (int, np.integer))
                or tile_budget < 1):
            raise ValueError(
                f"tile_budget must be a positive int, got {tile_budget!r}")
        self.spec = spec
        self.backend = backend
        self._root_key = (jax.random.PRNGKey(0) if key is None
                          else jnp.asarray(key))
        self._calls = 0
        self._dead: set = set()
        self._pending: List[_Request] = []
        self._next_rid = 0
        self._tile_budget = int(tile_budget)
        # optional CostModel: block sides come from the cost-model-aware
        # search instead of the fixed-(s,t) doubling rule (DESIGN.md §7)
        self._cost = cost
        self.failures: Dict[int, str] = {}
        self.stats = {"matmuls": 0, "blocks": 0, "flushes": 0,
                      "retiles": 0, "masks_dropped": 0,
                      "corrections": 0, "evicted_devices": 0,
                      "waves": 0, "padded_lanes": 0, "deferred_groups": 0,
                      "host_bytes": 0, "mesh_bytes": 0}

    # ------------------------------------------------------------- helpers
    def validate_survivors(self, survivors) -> np.ndarray:
        """Public survivor-mask validation (see ``MPCSpec``)."""
        return self.spec.validate_survivors(survivors)

    def fail(self, workers) -> None:
        """Mark logical workers dead for every later matmul/flush.

        Without a pool the ids are protocol slots; with a
        :class:`~repro.mpc.workers.WorkerPool` spec they are roster
        *device* ids, translated to slots through the placement (devices
        outside the placement only matter to elastic spare inventories).
        Local/sharded backends fold the dead set into each decode's
        survivor mask (phase-3 coded tolerance); the batched backend
        additionally reports attrition to its elastic pools, so spares and
        replan escalation engage exactly as under ``MPCEngine.fail``.
        """
        self._dead.update(int(w) for w in np.atleast_1d(
            np.asarray(workers, np.int64)).tolist())
        self.backend.fail(frozenset(self._dead))

    def _absorb_byzantine(self) -> None:
        """Surface the backend's verified-decode outcomes (DESIGN.md §9).

        After every dispatch round: mirror the backend's correction /
        eviction counters into :attr:`stats`, and route newly-detected
        liars through the session's own :meth:`fail` path — a caught liar
        IS attrition, reported in roster device ids for pool specs (the
        backend already speaks device ids) and slot ids otherwise, so
        spares/retune/replan escalation engages identically to a crash.
        """
        sched = getattr(self.backend, "scheduler_stats", None)
        if sched is not None:  # waves (DESIGN.md §10), bytes via the host
            s = sched()
            for k in ("waves", "padded_lanes", "deferred_groups",
                      "host_bytes", "mesh_bytes"):
                self.stats[k] = int(s.get(k, 0))
        counters = getattr(self.backend, "byzantine_stats", None)
        if counters is None:
            return
        c = counters()
        self.stats["corrections"] = int(c.get("corrections", 0))
        self.stats["evicted_devices"] = int(c.get("evicted_devices", 0))
        take = getattr(self.backend, "take_new_liars", None)
        liars = take() if take is not None else ()
        if liars:
            self.fail(sorted(liars))

    def _serve_ops(self, ops: List[BlockOp]) -> List[BlockOp]:
        """Fold session attrition into each block's decode mask at serve
        time (mirroring the engine, which folds pool attrition per flush).
        Backends that own their pool machinery skip the fold — their
        elastic pools already see the dead set."""
        if self.backend.handles_attrition or not self._dead:
            return ops
        alive = np.ones(self.spec.n_workers, bool)
        for w in self.spec.slots_for(self._dead):
            if w < alive.size:
                alive[w] = False
        return [dataclasses.replace(
            op, survivors=(alive if op.survivors is None
                           else alive & np.asarray(op.survivors, bool)))
            for op in ops]

    def _next_key(self, key) -> jnp.ndarray:
        if key is not None:
            return jnp.asarray(key)
        k = jax.random.fold_in(self._root_key, self._calls)
        return k

    # -------------------------------------------------------- one matmul
    def matmul(self, a, b, *, key=None, survivors: Optional[np.ndarray] = None,
               encoded: bool = False, m: Optional[int] = None):
        """``a @ b`` under MPC, any ``[..., r, k] × [..., k, c]`` shapes.

        Floats go through the spec field's fixed-point encode/decode; pass
        ``encoded=True`` to treat operands as field elements and get the
        exact ``(a @ b) mod p`` back (bit-exact, no fixed point).
        ``survivors`` is a bool ``[N]`` decode mask applied to every block;
        ``m`` overrides the spec/adapter block side for this call.
        """
        req = self._build_request(a, b, key=key, survivors=survivors,
                                  encoded=encoded, m=m)
        outs = []
        if req.ops:
            outs = self.backend.run_blocks(self._serve_ops(req.ops))
            self.stats["flushes"] += 1   # one backend dispatch round
            self._absorb_byzantine()
        for out in outs:
            if isinstance(out, BlockFailure):
                raise RuntimeError(out.reason)
        return req.build(outs)

    # ----------------------------------------------------- submit / flush
    def submit(self, a, b, *, key=None,
               survivors: Optional[np.ndarray] = None,
               encoded: bool = False, m: Optional[int] = None) -> int:
        """Queue one matmul; returns its request id (serve via :meth:`flush`)."""
        with span("session.submit", rid=self._next_rid):
            req = self._build_request(a, b, key=key, survivors=survivors,
                                      encoded=encoded, m=m)
        self._pending.append(req)
        return req.rid

    def pending(self) -> int:
        return len(self._pending)

    def flush(self) -> Dict[int, jnp.ndarray]:
        """Serve every queued request; returns ``{rid: result}``.

        All queued requests' blocks go to the backend as ONE op list (the
        batched backend turns that into one engine flush).  Failures are
        isolated per request in :attr:`failures` (``rid → reason``,
        replaced each flush), mirroring ``MPCEngine`` semantics.

        Replan drain (DESIGN.md §8): when session attrition has pushed the
        backing pool below N and the free re-tune prefers a *different*
        block side than the in-flight spec, queued requests are re-tiled
        at the new optimum before serving (``stats["retiles"]``) instead
        of pinning to the old ``m`` — the old group simply drains.
        """
        self._maybe_retile()
        queue, self._pending = self._pending, []
        self.failures = {}
        ops: List[BlockOp] = []
        for req in queue:
            ops.extend(req.ops)
        with span("session.flush", requests=len(queue), blocks=len(ops)):
            outs = []
            if ops:
                with span("backend.run_blocks"):
                    outs = self.backend.run_blocks(self._serve_ops(ops))
                self.stats["flushes"] += 1   # one backend dispatch round
                self._absorb_byzantine()

            results: Dict[int, jnp.ndarray] = {}
            pos = 0
            for req in queue:
                chunk = outs[pos: pos + len(req.ops)]
                pos += len(req.ops)
                bad = next((o for o in chunk if isinstance(o, BlockFailure)),
                           None)
                if bad is not None:
                    self.failures[req.rid] = bad.reason
                    continue
                with span("session.assemble", rid=req.rid):
                    results[req.rid] = req.build(chunk)
            return results

    # ------------------------------------------------------- replan drain
    def _maybe_retile(self) -> None:
        """Adopt a drain re-tune before tiling hits the backend.

        Only engages when (a) the session has reported attrition, (b) the
        backend can answer a free re-tune (``drain_spec``; the batched
        backend resolves it through its engine pools) and (c) that
        re-tune's optimal block side differs from the in-flight spec's.
        Queued requests holding their raw operands are then rebuilt under
        the new spec (same rids); per-request survivor masks sized for the
        old worker set are dropped (``stats["masks_dropped"]``).  For a
        pool spec the dead set is KEPT — the adopted spec carries the same
        original roster (its placement just avoids the dead devices), so
        device ids stay valid.  For an int-N spec the dead slot ids named
        workers of the old protocol and index nothing the new serving
        group runs on, so the set (and the backend's view of it) resets.
        """
        if not self._pending or not self._dead:
            return
        raws = [r.raw for r in self._pending
                if r.raw is not None and r.raw["m"] is None]
        if not raws:
            return
        # the largest queued workload drives the block side, like one
        # adapter call would
        pick = max(raws, key=lambda raw: raw["batch"] * int(
            np.prod(raw["shape"], dtype=np.int64)))
        new = self.backend.drain_spec(
            self.spec, pick["shape"], batch=pick["batch"],
            cost=self._cost, tile_budget=self._tile_budget)
        if new is None:
            return
        old_spec, self.spec = self.spec, new
        self.stats["retiles"] += 1
        if old_spec.pool is None:
            self._dead.clear()
            self.backend.fail(frozenset())   # reset the backend's view too
        queue, self._pending = self._pending, []
        for req in queue:
            raw = req.raw
            if raw is None or raw["m"] is not None:
                self._pending.append(req)  # pinned-m / degenerate: keep
                continue
            surv = raw["survivors"]
            if surv is not None:
                surv = None
                self.stats["masks_dropped"] += 1
            self.stats["blocks"] -= len(req.ops)
            self._pending.append(self._build_request(
                raw["a"], raw["b"], key=raw["key"], survivors=surv,
                encoded=raw["encoded"], m=None, rid=req.rid))

    # -------------------------------------------------- request construction
    def _build_request(self, a, b, *, key, survivors, encoded, m,
                       rid: Optional[int] = None) -> _Request:
        f = self.spec.field
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        raw_a, raw_b = a, b      # pre-normalization operands, for re-tiling
        a_vec, b_vec = a.ndim == 1, b.ndim == 1
        if a_vec:
            a = a[None, :]
        if b_vec:
            b = b[:, None]
        if a.ndim < 2 or b.ndim < 2 or a.shape[-1] != b.shape[-2]:
            raise ValueError(
                f"matmul shapes do not align: {a.shape} x {b.shape}")
        out_dtype = jnp.result_type(a.dtype, b.dtype)
        if not jnp.issubdtype(out_dtype, jnp.floating):
            out_dtype = jnp.float64
        with span("session.encode"):
            ea = a if encoded else f.encode(a)
            eb = b if encoded else f.encode(b)
            ea = jnp.asarray(ea, jnp.int64) % f.p
            eb = jnp.asarray(eb, jnp.int64) % f.p

        kdim = a.shape[-1]
        if b.ndim == 2:
            # the common serving shape: fold every leading dim of a into
            # rows — one 2-D tiled product regardless of batch depth
            lead = a.shape[:-1]
            r = int(np.prod(lead, dtype=np.int64)) if lead else 1
            pieces = [(ea.reshape(r, kdim), eb)]
            out_shape: Tuple[int, ...] = tuple(lead) + (b.shape[-1],)
        else:
            bshape = np.broadcast_shapes(a.shape[:-2], b.shape[:-2])
            eab = jnp.broadcast_to(
                ea, bshape + a.shape[-2:]).reshape((-1,) + a.shape[-2:])
            ebb = jnp.broadcast_to(
                eb, bshape + b.shape[-2:]).reshape((-1,) + b.shape[-2:])
            pieces = [(eab[i], ebb[i]) for i in range(eab.shape[0])]
            out_shape = tuple(bshape) + (a.shape[-2], b.shape[-1])
            r = a.shape[-2]
        c = b.shape[-1]

        b_folded = b.ndim == 2   # keep only the flag, not the operand
        if min(r, kdim, c) == 0 or not pieces:
            # np.matmul semantics without protocol work: an empty
            # contraction sums to zero, empty rows/cols give empty output
            if survivors is not None:
                self.spec.validate_survivors(survivors)
            zeros = jnp.zeros(out_shape, jnp.int64 if encoded else out_dtype)
            if b_vec:
                zeros = zeros[..., 0]
            if a_vec:
                zeros = zeros[0] if b_folded else zeros[..., 0, :]
            return self._finish_request([], lambda outs: zeros, rid=rid)

        if m is not None:
            # route the override through the spec so the s|m / t|m rule
            # lives in exactly one place
            block = self.spec.replace(m=int(m)).m
        elif self.spec.m:
            block = self.spec.m
        elif self._cost is not None:
            # mesh-shape-aware dispatch (DESIGN.md §8): a backend whose
            # per-block launch serializes (sharded waves of ceil(N/D))
            # scales the dispatch term of the block search
            cost = self._cost
            scale = self.backend.dispatch_scale(self.spec)
            if scale != 1.0 and hasattr(cost, "with_dispatch_scale"):
                cost = cost.with_dispatch_scale(scale)
            block = choose_block_cost(
                self.spec.s, self.spec.t, self.spec.z, self.spec.n_workers,
                r, kdim, c, cost=cost, batch=len(pieces),
                budget=self._tile_budget, pool=self.spec.pool,
                placement=self.spec.effective_placement)
        else:
            block = choose_block(self.spec.s, self.spec.t, r, kdim, c,
                                 budget=self._tile_budget)
        proto = self.spec.protocol(block)
        tm = TileMap(m=block, r=r, k=kdim, c=c)
        eff: Optional[np.ndarray] = None
        if survivors is not None:
            self.spec.validate_survivors(survivors)  # shape + threshold
            eff = np.asarray(survivors, bool)
        base = self._next_key(key)
        self._calls += 1

        n_ops = tm.n_blocks * len(pieces)
        # exact-fit single block: no tiling, no padding, no reassembly —
        # the facade collapses to one protocol call on the operands
        clean = n_ops == 1 and (r, kdim, c) == (block, block, block)
        ops: List[BlockOp] = []
        with span("session.tile"):
            for pa, pb in pieces:
                if clean:
                    ops.append(BlockOp(proto=proto, a=pa.T, b=pb, key=base,
                                       survivors=eff))
                    continue
                ta = tile_blocks(pa, block)          # [gr, gk, m, m]
                tb = tile_blocks(pb, block)          # [gk, gc, m, m]
                for i in range(tm.gr):
                    # one transposed A tile per (i, l), shared by every
                    # column block (a wide projection would otherwise copy
                    # it gc times)
                    a_row = [ta[i, l].T for l in range(tm.gk)]
                    for j in range(tm.gc):
                        for l in range(tm.gk):
                            # single-block calls consume the caller's key
                            # directly: bit-identical to protocol.run
                            bk = (base if n_ops == 1
                                  else jax.random.fold_in(base, len(ops)))
                            ops.append(BlockOp(
                                proto=proto, a=a_row[l], b=tb[l, j],
                                key=bk, survivors=eff))

        n_pieces = len(pieces)

        def build(outs: List[jnp.ndarray]) -> jnp.ndarray:
            per = tm.n_blocks
            mats = (outs if clean else
                    [assemble(tm, outs[i * per:(i + 1) * per], f.p)
                     for i in range(n_pieces)])
            y = mats[0] if n_pieces == 1 else jnp.stack(mats)
            if encoded:
                out = y.reshape(out_shape)
            else:
                out = f.decode(y, products=2).reshape(out_shape).astype(
                    out_dtype)
            if b_vec:
                out = out[..., 0]
            if a_vec:
                out = out[0] if b_folded else out[..., 0, :]
            return out

        raw = {"a": raw_a, "b": raw_b, "key": key, "survivors": survivors,
               "encoded": encoded, "m": m, "shape": (r, kdim, c),
               "batch": n_pieces}
        return self._finish_request(ops, build, raw=raw, rid=rid)

    def _finish_request(self, ops: List[BlockOp], build: Callable, *,
                        raw: Optional[Dict[str, Any]] = None,
                        rid: Optional[int] = None) -> _Request:
        if rid is None:  # a drain re-tile reuses the caller-visible rid
            rid = self._next_rid
            self._next_rid += 1
            self.stats["matmuls"] += 1
        self.stats["blocks"] += len(ops)
        return _Request(rid=rid, ops=ops, build=build, raw=raw)


# ================================================================= connect
def connect(spec: MPCSpec, backend: str = "local", **opts) -> MPCSession:
    """Open an :class:`MPCSession` over one of the pluggable backends.

    ``spec`` is an :class:`MPCSpec` — hand-built or autotuned
    (``connect(MPCSpec.tune(N, z, shape))``).  ``backend``: ``"local"``
    (default; ``mode="fused"|"pallas"|"reference"``), ``"sharded"``
    (requires ``mesh=``, optional ``axis``, ``wire_dtype``, ``prg_masks``)
    ``"batched"`` (optional ``spares``, ``max_batch``) or ``"remote"``
    (out-of-process workers over the message-framed transport; optional
    ``spawn="thread"|"process"``, ``pipelined``, ``recorder``, see
    :class:`repro.mpc.backends.RemoteBackend` and DESIGN.md §13) — or an
    already-constructed backend instance.  Session-level options: ``key``
    (base PRNG key), ``tile_budget`` (shape-adapter dispatch cap, validated
    here so misconfiguration fails at connect time) and ``cost`` (a
    :class:`repro.mpc.autotune.CostModel`; block sides then come from the
    cost-model-aware search — scaled by the backend's ``dispatch_scale``
    and weighted by the spec's pool when present — and the batched
    backend's engine re-tunes under the same weights on attrition).  With
    ``cost`` set the budget caps the *whole* workload's dispatches —
    batch × tiles, warning on clamp — whereas the default path caps
    per-piece tiles only (:func:`repro.mpc.tiling.choose_block_cost`).
    A spec carrying a :class:`repro.mpc.workers.WorkerPool` changes
    ``fail`` ids to roster device ids and makes the batched backend's
    elastic pools provision high-capacity spares (DESIGN.md §8).
    A spec with ``adversaries > 0`` routes every decode through MAC
    verification on the local and batched backends (DESIGN.md §9);
    ``injector=`` (a :class:`repro.mpc.byzantine.FaultInjector`) wraps the
    backend's shares in a seeded corruption schedule for testing — the
    sharded backend supports neither and is rejected here.
    """
    from .backends import resolve_backend

    key = opts.pop("key", None)
    tile_budget = opts.pop("tile_budget", DEFAULT_TILE_BUDGET)
    cost = opts.pop("cost", None)
    if backend in ("sharded", "remote") and (
            spec.adversaries or opts.get("injector") is not None):
        # neither the mesh runner nor the wire transport carries the MAC
        # tags verification needs (DESIGN.md §9); silently serving
        # unverified shares under a Byzantine spec would defeat the
        # budget's whole point — fail at connect time
        raise ValueError(
            f"the {backend} backend does not verify shares: use the local "
            "or batched backend for specs with adversaries > 0 / an "
            "injector")
    if cost is not None and backend == "batched":
        # the engine re-tunes under the same objective it serves with
        opts.setdefault("cost", cost)
    be = resolve_backend(backend, **opts)
    engine = getattr(be, "engine", None)
    if cost is not None and engine is not None and engine.cost is None:
        # a pre-constructed batched backend: align its re-tune objective
        # with the session's, unless the engine was built with its own
        engine.cost = cost
    return MPCSession(spec, be, key=key, tile_budget=tile_budget, cost=cost)
