"""The program's own spans and named scopes in a profiler trace.

:func:`bench.harness.trace.load` keeps each device's ``XLA Ops`` and
``XLA Modules`` intervals and the benchmark's ``bench.*`` host spans.  The
program marks more, on the same clock:

* host spans ``mpc.*`` (``repro.runtime.span``: the session's submit,
  encode, tile, flush and assemble, each backend block, the sharded
  runner's shares, fetch, upload and decode), each carrying the ids of
  its request (``rid``, ``block``) as TraceMe metadata;
* named scopes on the device work (``mpc.encode``, ``mpc.worker_compute``,
  ``mpc.exchange``, ``mpc.decode``, ``mpc.tags``; ``field_gemm`` with
  ``field_gemm.split``, ``.dot`` and ``.recombine``), which reach the
  compiled HLO as each op's op-name path
  (``jit(fused)/mpc.encode/field_gemm/field_gemm.dot/dot_general``).  A
  TPU v5e trace names an ``XLA Ops`` event by its HLO instruction alone
  (its stats hold no op-name path), so :func:`load` maps each op through
  its module's compiled HLO text (:func:`hlo_op_paths`).

:func:`load` reads both into a :class:`ProgramTrace`; the functions below
reduce it, with :func:`idle_by_innermost` giving each idle instant of a
chip to the innermost host span that covers it.  Like ``trace.py`` they
are pure, so a trace built by hand checks them.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as tr
from .trace import Span

PROGRAM_PREFIX = "mpc."
#: the stats of a program span that name its request
ID_STATS = ("rid", "block")
#: the device scopes of the protocol's stages, in the order they run
STAGES = ("mpc.encode", "mpc.worker_compute", "mpc.exchange", "mpc.decode")


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                 # mpc.* host spans
    ids: List[Dict[str, int]]         # each span's rid / block, same order
    op_scopes: Dict[int, List[Span]]  # device id -> (op-name path, start, end)


def span_name(name: str) -> str:
    """A TraceMe's name without the metadata it may carry (``name#k=v#``)."""
    return name.split("#", 1)[0]


def hlo_op_paths(hlo: str) -> Dict[str, str]:
    """Each instruction's op-name path in compiled HLO text
    (``Compiled.as_text()``).  An instruction with no ``op_name`` of its
    own, as a fusion often has, takes its called computation's root's."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0].lstrip("%")
            continue
        if not line.startswith("  ") or " = " not in line:
            continue
        head, _, rest = line.strip().partition(" = ")
        name = head.removeprefix("ROOT ").lstrip("%")
        if head.startswith("ROOT ") and comp is not None:
            roots[comp] = name
        m = re.search(r'op_name="([^"]*)"', rest)
        if m:
            own[name] = m.group(1)
        m = re.search(r"calls=%?([\w.\-]+)", rest)
        if m:
            calls[name] = m.group(1)

    def path(name: str, depth: int = 0) -> str:
        if name in own or depth > 16:
            return own.get(name, "")
        root = roots.get(calls.get(name, ""))
        return path(root, depth + 1) if root else ""

    return {name: path(name) for name in set(own) | set(calls)}


def op_instruction(name: str) -> str:
    """An ``XLA Ops`` event's HLO instruction name (a TPU trace names an
    op by its whole instruction, ``%fusion.12 = (...) fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str, op_paths: Optional[Dict[str, Dict[str, str]]] = None
         ) -> ProgramTrace:
    """The program's spans and op scopes of the newest ``.xplane.pb``
    under ``log_dir``; ``op_paths[module]`` is :func:`hlo_op_paths` of the
    module's compiled HLO, by module name without its program id."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)),
                        op_paths or {})


def from_profile(data, op_paths: Dict[str, Dict[str, str]]) -> ProgramTrace:
    """:func:`load` of a ``jax.profiler.ProfileData`` already read: each
    device op gets the path of its instruction in the module it ran in
    (the ``XLA Modules`` event that covers its start), or ``""``."""
    spans: List[Span] = []
    ids: List[Dict[str, int]] = []
    op_scopes: Dict[int, List[Span]] = {}
    for plane in data.planes:
        dev = tr.DEVICE_PLANE.match(plane.name)
        if dev is None:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for e in line.events:
                        name = span_name(e.name)
                        if name.startswith(PROGRAM_PREFIX):
                            spans.append((name, int(e.start_ns),
                                          int(e.end_ns)))
                            ids.append({k: int(v) for k, v in e.stats
                                        if k in ID_STATS})
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted(((re.sub(r"\(\d+\)$", "", e.name), int(e.start_ns),
                        int(e.end_ns)) for e in (lines["XLA Modules"].events
                                                 if "XLA Modules" in lines
                                                 else ())),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        ops = op_scopes.setdefault(int(dev.group(1)), [])
        for e in lines["XLA Ops"].events:
            a, b = int(e.start_ns), int(e.end_ns)
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] > a else ""
            ops.append((op_paths.get(mod, {}).get(op_instruction(e.name), ""),
                        a, b))
    return ProgramTrace(spans=spans, ids=ids, op_scopes=op_scopes)


# ------------------------------------------------------------ device scopes
def in_scope(path: str, scope: str) -> bool:
    """Whether an op-name path runs under the named scope ``scope`` (a
    whole segment: ``field_gemm`` does not match ``field_gemm.dot`` alone,
    but every op under ``field_gemm.dot`` is also under ``field_gemm``)."""
    return scope in path.split("/")


def scope_ns(ops: Iterable[Span], scope: str, lo: int, hi: int) -> int:
    """Covered time of the ops under ``scope`` inside ``[lo, hi]``."""
    return tr.covered_ns((o for o in ops if in_scope(o[0], scope)), lo, hi)


def scope_ms_per_product(pt: ProgramTrace, devices: Sequence[int],
                         scope: str, lo: int, hi: int, completed: int
                         ) -> Optional[float]:
    """Device milliseconds under ``scope`` per completed product, on the
    chip where they took longest; ``None`` where no op ran under it."""
    if not completed:
        return None
    ns = max(scope_ns(pt.op_scopes.get(d, []), scope, lo, hi) for d in devices)
    return ns / 1e6 / completed if ns else None


def unscoped(pt: ProgramTrace, device: int, lo: int, hi: int,
             k: int = 10) -> List[List]:
    """The ``k`` op-name paths with the most time, in seconds, among the
    ops of ``device`` under no stage scope (eager ops among them)."""
    tot: Dict[str, int] = {}
    for path, a, b in tr.clip(pt.op_scopes.get(device, []), lo, hi):
        if not any(in_scope(path, s) for s in STAGES + ("mpc.tags",)):
            tot[path] = tot.get(path, 0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[path, ns / 1e9] for path, ns in ranked]


# --------------------------------------------------------------- host spans
def span_ns(pt: ProgramTrace, names: Sequence[str], lo: int, hi: int) -> int:
    """Covered host time of the program spans named in ``names``."""
    return tr.covered_ns((s for s in pt.spans if s[0] in names), lo, hi)


def innermost(spans: Sequence[Span]) -> List[Span]:
    """The host timeline cut where a span starts or ends, each piece named
    after the innermost span covering it (the one that started last; of
    two that start together, the one that ends first)."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    points = sorted({s[1] for s in spans} | {s[2] for s in spans})
    heap: List[Tuple[int, int, int, str]] = []
    out: List[Span] = []
    i = 0
    for x, nxt in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= x:
            name, a, b = order[i]
            heapq.heappush(heap, (-a, -i, b, name))
            i += 1
        while heap and heap[0][2] <= x:
            heapq.heappop(heap)
        if heap:
            name = heap[0][3]
            if out and out[-1][0] == name and out[-1][2] == x:
                out[-1] = (name, out[-1][1], nxt)
            else:
                out.append((name, x, nxt))
    return out


def idle_by_innermost(trace: tr.Trace, pt: Optional[ProgramTrace],
                      device: int, lo: int, hi: int, k: int = 10
                      ) -> List[List]:
    """Idle seconds of ``device`` by what the host was doing then: each
    idle instant goes to the innermost span covering it, among the
    ``bench.*`` spans other than the window and the program's ``mpc.*``
    spans, and what none covers to ``host.other``.  With ``bench.*`` spans
    alone, which never nest, this is :func:`bench.harness.trace.idle_by_host`.
    """
    spans = [s for s in trace.host if s[0] != tr.HOST_PREFIX + "window"]
    if pt is not None:
        spans += pt.spans
    pieces = innermost(tr.clip(spans, lo, hi))
    tot: Dict[str, int] = {}
    j = 0
    for g0, g1 in tr.gaps(trace, device, lo, hi):
        left = g1 - g0
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        i = j
        while i < len(pieces) and pieces[i][1] < g1:
            name, a, b = pieces[i]
            ns = min(b, g1) - max(a, g0)
            if ns > 0:
                tot[name] = tot.get(name, 0) + ns
                left -= ns
            i += 1
        if left > 0:
            tot["host.other"] = tot.get("host.other", 0) + left
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]
