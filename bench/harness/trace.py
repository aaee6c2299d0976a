"""From a profiler trace to numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace`: for each device, the intervals of its ``XLA Ops`` and
``XLA Modules`` lines, and the benchmark's own host spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation`` on the same clock).  The
functions below reduce a :class:`Trace` to the per-layer metrics; they
are pure, so a small trace built by hand checks them without a device.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Span = Tuple[str, int, int]           # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Span]]        # device id -> XLA op intervals
    modules: Dict[int, List[Span]]    # device id -> XLA module intervals
    host: List[Span]                  # bench.* spans

    def window(self) -> Tuple[int, int]:
        """The ``bench.window`` span: where the measured window lies."""
        for name, a, b in self.host:
            if name == HOST_PREFIX + "window":
                return a, b
        raise ValueError("the trace holds no bench.window span")


def load(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir`` as a :class:`Trace`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: Dict[int, List[Span]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                into = ops if line.name == "XLA Ops" else modules
                into.setdefault(int(dev.group(1)), []).extend(
                    (e.name, int(e.start_ns), int(e.end_ns)) for e in line.events)
            elif dev is None and plane.name.startswith("/host"):
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(ops=ops, modules=modules, host=host)


# ---------------------------------------------------------------- intervals
def clip(spans: Iterable[Span], lo: int, hi: int) -> List[Span]:
    out = []
    for name, a, b in spans:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(spans: Iterable[Span]) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: List[List[int]] = []
    for _, a, b in sorted(spans, key=lambda s: s[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered_ns(spans: Iterable[Span], lo: int, hi: int) -> int:
    return sum(b - a for a, b in union(clip(spans, lo, hi)))


def busy_ns(trace: Trace, devices: Sequence[int], lo: int, hi: int) -> float:
    """Mean over ``devices`` of the time some operation ran on it."""
    return sum(covered_ns(trace.ops.get(d, []), lo, hi)
               for d in devices) / len(devices)


def gaps(trace: Trace, device: int, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of one device inside ``[lo, hi]``."""
    out, cur = [], lo
    for a, b in union(clip(trace.ops.get(device, []), lo, hi)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def matching_ns(spans: Iterable[Span], pattern: re.Pattern, lo: int, hi: int
                ) -> int:
    """Covered time of the spans whose name matches ``pattern``."""
    return covered_ns((s for s in spans if pattern.search(s[0])), lo, hi)


def per_device_max(spans: Dict[int, List[Span]], devices: Sequence[int],
                   pattern: re.Pattern, lo: int, hi: int) -> int:
    """The largest, over ``devices``, covered time of matching spans."""
    return max(matching_ns(spans.get(d, []), pattern, lo, hi) for d in devices)


# --------------------------------------------------------------- breakdown
def _op_kind(name: str) -> str:
    """An op's kind: its HLO instruction name (a TPU trace names an op by
    its whole instruction, ``%fusion.12 = (...) fusion(...)``) without the
    instruction number."""
    return re.sub(r"[.:]\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _module_name(name: str) -> str:
    """A module's name without its program id (``jit_fused(123)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def top_ops(trace: Trace, devices: Sequence[int], lo: int, hi: int,
            k: int = 10) -> List[List]:
    """The ``k`` device operations that took the most time, in seconds,
    summed over ``devices``; each named ``<module>:<op kind>`` after the
    XLA module it ran in."""
    tot: Dict[str, int] = {}
    for d in devices:
        mods = sorted(trace.modules.get(d, []), key=lambda s: s[1])
        starts = [m[1] for m in mods]
        for name, a, b in clip(trace.ops.get(d, []), lo, hi):
            i = bisect.bisect_right(starts, a) - 1
            kind = _op_kind(name)
            if i >= 0 and mods[i][2] > a:
                kind = f"{_module_name(mods[i][0])}:{kind}"
            tot[kind] = tot.get(kind, 0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_by_host(trace: Trace, device: int, lo: int, hi: int,
                 k: int = 10) -> List[List]:
    """Idle seconds of ``device`` by what the host was doing then: each
    gap's time is given to the ``bench.*`` spans (other than the window)
    that overlap it, and what none covers to ``host.other``."""
    spans = [s for s in trace.host if s[0] != HOST_PREFIX + "window"]
    tot: Dict[str, int] = {}
    for g0, g1 in gaps(trace, device, lo, hi):
        left = g1 - g0
        for name, a, b in clip(spans, g0, g1):
            tot[name] = tot.get(name, 0) + (b - a)
            left -= b - a
        if left > 0:
            tot["host.other"] = tot.get("host.other", 0) + left
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]
