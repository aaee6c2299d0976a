"""Reductions the per-layer readers (``bench/metrics/*.py``) share.

Each returns ``None`` where the run holds nothing to read (no trace, no
completed product, no such program or operation in the trace), and the
harness then leaves the metric out of the result line.
"""
from __future__ import annotations

import re
from typing import Optional

from . import trace as tr

#: the XLA modules of the protocol's stage programs (``ProtocolStages`` of
#: ``repro.mpc.planner``, vmapped or not, and ``ShardedCMPC``'s step)
STAGE_MODULES = re.compile(
    r"^jit_(fused|front|decode|tags|encode|worker_compute|exchange|step)"
    r"(\(|\.|$)")

#: the device operations that move data between chips: an op is named by
#: its HLO instruction (``%reduce-scatter.3 = ...``), async halves
#: (``-start``/``-done``) and fusions around a collective included
COLLECTIVE_OPS = re.compile(
    r"^%?[\w.-]*(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)")


def idle_share(run) -> Optional[float]:
    """Percent of the window in which no operation ran, mean over chips."""
    if run.trace is None or not any(run.trace.ops.get(d) for d in run.devices):
        return None
    lo, hi = run.window_ns
    return 100.0 * (1.0 - tr.busy_ns(run.trace, run.devices, lo, hi) / (hi - lo))


def roofline_share(run) -> Optional[float]:
    """Percent of the stage programs' device time per product that the
    window's field GEMM work needs at the chip's peaks
    (``bench/harness/work.py``)."""
    if not run.peaks:
        return None
    stage_ms = stage_ms_per_product(run)
    if not stage_ms:
        return None
    least_s, _ = run.least_time_per_product()
    return 100.0 * least_s * 1e3 / stage_ms


def stage_ms_per_product(run) -> Optional[float]:
    """Device milliseconds of the stage programs per completed product,
    on the busiest chip."""
    if run.trace is None or not run.completed:
        return None
    lo, hi = run.window_ns
    ns = tr.per_device_max(run.trace.modules, run.devices, STAGE_MODULES, lo, hi)
    return ns / 1e6 / run.completed if ns else None


def collective_ms_per_product(run) -> Optional[float]:
    """Device milliseconds of collective operations per completed
    product, on the chip where they took longest; ``None`` on one chip or
    where the trace holds none."""
    if run.trace is None or not run.completed or len(run.devices) < 2:
        return None
    lo, hi = run.window_ns
    ns = tr.per_device_max(run.trace.ops, run.devices, COLLECTIVE_OPS, lo, hi)
    return ns / 1e6 / run.completed if ns else None


def routed_macs(run) -> int:
    """Multiply-adds of the completed routed requests' products as the
    plain layer has them: ``Σ n_e·K·F`` over each request's held experts,
    ``n_e`` the rows routed to expert ``e``; 0 where the run has none."""
    shapes = run.cell.config["projections"]
    total = 0
    for r in run.requests:
        if r.completed and getattr(r, "tokens", ()):
            k, f = shapes[r.projection]
            total += sum(len(rows) for rows in r.tokens) * k * f
    return total
