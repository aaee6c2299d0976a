"""What the window drives: the system under test, or the control.

Both serve ``submit(a, b) -> rid`` and ``flush() -> ({rid: y}, {rid:
reason})``, as the user entry point ``repro.mpc.connect`` →
``MPCSession.submit`` / ``MPCSession.flush`` does.
"""
from __future__ import annotations

import collections
from typing import Dict, Tuple

import numpy as np

from . import reference


class ProgramServer:
    """One ``MPCSession`` over the configuration's spec and backend.

    The benchmark wraps the backend's ``run_blocks`` to count the coded
    blocks it is handed by plan shape ``(n, s, t, z, m)``: the work that
    the roofline share and the per-product device times divide by.
    """

    def __init__(self, config: dict, devices, key):
        from repro.mpc import MPCSpec, connect
        from repro.mpc.field import Field

        sp = config["spec"]
        self.spec = MPCSpec(s=sp["s"], t=sp["t"], z=sp["z"], lam=sp["lam"],
                            scheme=sp["scheme"],
                            field=Field(sp["p"], sp["frac_bits"]))
        if self.spec.n_workers != config["n_workers"]:
            raise ValueError(f"the spec gives N={self.spec.n_workers}, the "
                             f"configuration states {config['n_workers']}")
        opts = dict(config.get("backend_options", {}))
        if config["backend"] == "sharded":
            from jax.sharding import Mesh

            opts["mesh"] = Mesh(np.asarray(devices), ("model",))
        self.session = connect(self.spec, backend=config["backend"], key=key,
                               **opts)
        self.blocks: Dict[Tuple[int, ...], int] = collections.Counter()
        backend = self.session.backend
        run_blocks = backend.run_blocks

        def counted(ops):
            for op in ops:
                pr = op.proto
                self.blocks[(pr.n_workers, pr.s, pr.t, pr.z, pr.m)] += 1
            return run_blocks(ops)

        backend.run_blocks = counted

    def submit(self, a, b) -> int:
        return self.session.submit(a, b)

    def flush(self):
        out = self.session.flush()
        return out, dict(self.session.failures)

    def counters(self) -> Dict[str, int]:
        """The program's own counters (session and engine)."""
        out = {k: int(v) for k, v in self.session.stats.items()}
        out.update({f"engine.{k}": int(v) for k, v in
                    self.session.backend.scheduler_stats().items()})
        return out


class ControlServer:
    """The reference in the program's place, one precision lower: operands
    rounded to bfloat16 in a program of their own
    (:func:`bench.harness.reference.control_operand`), then multiplied
    (:func:`bench.harness.reference.control_product`)."""

    def __init__(self, config: dict):
        import jax

        f = config["spec"]["frac_bits"]
        self._round = jax.jit(lambda x: reference.control_operand(x, f))
        self._fn = jax.jit(lambda qa, qb: reference.control_product(qa, qb, f))
        self._queue: Dict[int, tuple] = {}
        self._next = 0
        self.blocks: Dict[Tuple[int, ...], int] = collections.Counter()

    def submit(self, a, b) -> int:
        rid, self._next = self._next, self._next + 1
        self._queue[rid] = (a, b)
        return rid

    def flush(self):
        queue, self._queue = self._queue, {}
        return {rid: self._fn(self._round(a), self._round(b))
                for rid, (a, b) in queue.items()}, {}

    def counters(self) -> Dict[str, int]:
        return {}
