"""The program's spans and scopes in a trace: the reductions on small
traces worked out by hand, and a CPU profiler run of the program read
back."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, scopes, trace as tr, traffic  # noqa: E402
from bench.harness.runner import RunView  # noqa: E402

BENCH = cells.load_benchmark()

# device 0 is busy over [0, 150] and [300, 400] of a 1000 ns window; the
# host flushes over [150, 300] and sleeps over [500, 900]
BENCH_ONLY = tr.Trace(
    ops={0: [("fusion.1", 0, 100), ("fusion.2", 50, 150),
             ("copy.3", 300, 400)]},
    modules={0: [("jit_fused(12)", 0, 160), ("jit_step(3)", 300, 400)]},
    host=[("bench.window", 0, 1000), ("bench.flush", 150, 300),
          ("bench.sleep", 500, 900)])

# inside that flush the program fetches over [160, 260], and uploads over
# [260, 290] inside a block span [155, 295]; its ops carry scope paths
PROGRAM = scopes.ProgramTrace(
    spans=[("mpc.block", 155, 295), ("mpc.sharded.fetch", 160, 260),
           ("mpc.sharded.upload", 260, 290), ("mpc.session.submit", 0, 40)],
    ids=[{"block": 0}, {}, {}, {"rid": 7}],
    op_scopes={
        0: [("jit(fused)/mpc.encode/field_gemm/field_gemm.dot/dot_general",
             0, 100),
            ("jit(fused)/mpc.encode/field_gemm/field_gemm.recombine/add",
             50, 150),
            ("jit(fused)/mpc.decode/field_gemm/field_gemm.split/and",
             300, 350),
            ("jit(remainder)/rem", 350, 400)],
        1: [("jit(step)/mpc.encode/field_gemm/field_gemm.dot/dot_general",
             0, 300)]})


def _view(trace, devices, completed=2, **kw):
    reqs = [traffic.Request(index=i, projection="up", operand=0, sent_s=0.0,
                            done_s=1.0) for i in range(completed)]
    fields = dict(cell=cells.resolve(BENCH, BENCH["workloads"][0]["name"]),
                  requests=reqs, trace=trace,
                  window_ns=trace.window() if trace else None,
                  devices=devices, counters={}, spans={}, blocks={}, peaks={})
    fields.update(kw)
    return RunView(**fields)


def test_bench_spans_alone_give_what_idle_by_host_gives():
    want = [["bench.sleep", 400e-9], ["host.other", 200e-9],
            ["bench.flush", 150e-9]]
    assert tr.idle_by_host(BENCH_ONLY, 0, 0, 1000) == want
    assert scopes.idle_by_innermost(BENCH_ONLY, None, 0, 0, 1000) == want
    empty = scopes.ProgramTrace(spans=[], ids=[], op_scopes={})
    assert scopes.idle_by_innermost(BENCH_ONLY, empty, 0, 0, 1000) == want


def test_a_gap_goes_to_the_innermost_span_only():
    # the gap [150, 300]: the flush alone over [150, 155) and [295, 300),
    # the block over [155, 160) and [290, 295), the fetch over [160, 260),
    # the upload over [260, 290)
    got = dict(map(tuple, scopes.idle_by_innermost(BENCH_ONLY, PROGRAM, 0,
                                                   0, 1000)))
    assert got == pytest.approx({
        "bench.sleep": 400e-9, "host.other": 200e-9,
        "mpc.sharded.fetch": 100e-9, "mpc.sharded.upload": 30e-9,
        "mpc.block": 10e-9, "bench.flush": 10e-9})
    assert sum(got.values()) == pytest.approx(750e-9)    # all the idle time


def test_innermost_pieces():
    assert scopes.innermost([("a", 0, 10), ("b", 2, 4), ("c", 2, 3)]) == [
        ("a", 0, 2), ("c", 2, 3), ("b", 3, 4), ("a", 4, 10)]
    assert scopes.innermost([("a", 0, 5), ("b", 7, 9)]) == [
        ("a", 0, 5), ("b", 7, 9)]


def test_scope_time_per_product_on_the_busiest_chip():
    ms = scopes.scope_ms_per_product
    # device 0: mpc.encode ops cover [0, 150]; device 1: [0, 300]
    assert ms(PROGRAM, [0], "mpc.encode", 0, 1000, 2) == pytest.approx(75e-6)
    assert ms(PROGRAM, [0, 1], "mpc.encode", 0, 1000, 2) == pytest.approx(150e-6)
    assert ms(PROGRAM, [0], "field_gemm", 0, 1000, 1) == pytest.approx(200e-6)
    assert ms(PROGRAM, [0], "field_gemm.dot", 0, 1000, 1) == pytest.approx(100e-6)
    assert ms(PROGRAM, [0], "mpc.decode", 0, 1000, 1) == pytest.approx(50e-6)
    # a whole segment: no op is under a scope named "field"
    assert ms(PROGRAM, [0], "field", 0, 1000, 1) is None
    assert ms(PROGRAM, [0], "mpc.exchange", 0, 1000, 1) is None
    assert ms(PROGRAM, [0], "mpc.encode", 0, 1000, 0) is None
    assert scopes.unscoped(PROGRAM, 0, 0, 1000) == [["jit(remainder)/rem",
                                                     50e-9]]


def test_program_span_time():
    both = ("mpc.sharded.fetch", "mpc.sharded.upload")
    assert scopes.span_ns(PROGRAM, both, 0, 1000) == 130
    assert scopes.span_ns(PROGRAM, both, 200, 1000) == 90
    assert scopes.span_ns(PROGRAM, ("mpc.engine",), 0, 1000) == 0


def test_trace_me_metadata_is_cut_from_a_name():
    assert scopes.span_name("mpc.session.submit#rid=3#") == "mpc.session.submit"
    assert scopes.span_name("mpc.block") == "mpc.block"


REC = "jit(fused)/mpc.exchange/field_gemm/field_gemm.recombine/add"
DOT = "jit(fused)/mpc.encode/field_gemm/field_gemm.dot/dot_general"
HLO = f"""HloModule jit_fused, is_scheduled=true

%fused_computation.3 (p.1: s64[4]) -> s64[4] {{
  %p.1 = s64[4]{{0}} parameter(0)
  ROOT %add.2 = s64[4]{{0}} add(%p.1, %p.1), metadata={{op_name="{REC}"}}
}}

ENTRY %main.9 (a.1: s8[4,4]) -> s64[4] {{
  %a.1 = s8[4,4]{{1,0}} parameter(0), metadata={{op_name="a"}}
  %dot.4 = s32[4]{{0}} dot(%a.1, %a.1), metadata={{op_name="{DOT}"}}
  %fusion.3 = s64[4]{{0}} fusion(%dot.4), kind=kLoop, calls=%fused_computation.3
  ROOT %copy.5 = s64[4]{{0}} copy(%fusion.3)
}}
"""


class _Event:
    def __init__(self, name, a, b, stats=()):
        self.name, self.start_ns, self.end_ns, self.stats = name, a, b, stats


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_op_paths_from_compiled_hlo():
    paths = scopes.hlo_op_paths(HLO)
    assert paths["dot.4"] == DOT
    # a fusion with no op_name of its own takes its root's
    assert paths["fusion.3"] == REC
    assert "copy.5" not in paths and paths["add.2"] == paths["fusion.3"]
    assert scopes.op_instruction("%fusion.3 = s64[4]{0} fusion(s32[4] %dot.4)"
                                 ) == "fusion.3"


def test_profile_ops_mapped_through_their_module():
    data = type("Profile", (), {"planes": [
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Event("jit_fused(77)", 0, 100),
                                  _Event("jit_remainder(5)", 200, 300)]),
            _Line("XLA Ops", [_Event("%dot.4 = s32[4]{0} dot(...)", 0, 40),
                              _Event("%fusion.3 = s64[4]{0} fusion(...)", 40, 90),
                              _Event("%copy.5 = s64[4]{0} copy(...)", 90, 100),
                              _Event("%dot.4 = f32[4]{0} dot(...)", 200, 250)])]),
        _Plane("/host:CPU", [_Line("python", [
            _Event("mpc.session.submit#rid=3#", 0, 10, [("rid", 3)]),
            _Event("bench.flush", 10, 20),
            _Event("mpc.block", 20, 30, [("block", 1), ("other", 9)])])])]})()
    pt = scopes.from_profile(data, {"jit_fused": scopes.hlo_op_paths(HLO)})
    paths = [p for p, _, _ in pt.op_scopes[0]]
    assert scopes.in_scope(paths[0], "field_gemm.dot")
    assert scopes.in_scope(paths[1], "mpc.exchange")
    assert paths[2:] == ["", ""]        # no op_name; another module's op
    assert pt.spans == [("mpc.session.submit", 0, 10), ("mpc.block", 20, 30)]
    assert pt.ids == [{"rid": 3}, {"block": 1}]


def test_host_mib_per_product():
    read = cells.metric_reader("sharded.host_mib_per_product")
    view = _view(BENCH_ONLY, [0], completed=4,
                 counters={"host_bytes": 4 * 1184 * 2**20})
    assert read(view) == 1184.0
    assert read(_view(BENCH_ONLY, [0])) is None            # no such counter
    assert read(_view(BENCH_ONLY, [0], completed=0,
                      counters={"host_bytes": 1})) is None


PROFILED = r"""
import json, sys, tempfile
sys.path[:0] = [{root!r}, {src!r}]
import jax, numpy as np
from jax.sharding import Mesh
from repro.mpc import MPCSpec, connect
from bench.harness import scopes, trace as tr

m = 8
sess = connect(MPCSpec(s=2, t=2, z=2, m=m), backend="sharded",
               mesh=Mesh(np.asarray(jax.devices()), ("model",)))
rng = np.random.default_rng(0)
pairs = [(rng.standard_normal((8, 16)), rng.standard_normal((16, 8)))
         for _ in range(2)]
sess.submit(*pairs[0]); sess.flush()              # compiled before tracing
before = {{k: sess.stats[k] for k in ("host_bytes", "mesh_bytes")}}
log_dir = tempfile.mkdtemp()
jax.profiler.start_trace(log_dir)
rids = [sess.submit(a, b) for a, b in pairs]
out = sess.flush()
jax.block_until_ready(list(out.values()))
jax.profiler.stop_trace()
pt = scopes.load(log_dir)
print(json.dumps({{
    "rids": rids,
    "spans": [[name, ids] for (name, _, _), ids in zip(pt.spans, pt.ids)],
    "bench_host": tr.load(log_dir).host,
    "host_bytes": sess.stats["host_bytes"] - before["host_bytes"],
    "mesh_bytes": sess.stats["mesh_bytes"] - before["mesh_bytes"],
    "blocks": 2 * 2, "mt": m // 2}}))
"""


def test_profiled_flush_on_four_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = PROFILED.format(root=str(ROOT), src=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    spans = res["spans"]
    names = [name for name, _ in spans]
    for rid in res["rids"]:
        assert ["mpc.session.submit", {"rid": rid}] in spans
        assert ["mpc.session.assemble", {"rid": rid}] in spans
    for name in ("mpc.session.encode", "mpc.session.tile", "mpc.session.flush",
                 "mpc.backend.run_blocks", "mpc.sharded.shares",
                 "mpc.sharded.gather", "mpc.sharded.decode"):
        assert name in names, name
    # one gather and one block span per block, numbered within the flush
    assert names.count("mpc.sharded.gather") == res["blocks"]
    assert sorted(ids["block"] for name, ids in spans
                  if name == "mpc.block") == list(range(res["blocks"]))
    assert res["bench_host"] == []      # trace.load keeps bench.* alone
    # no share crosses the host; of the decode quorum's t²+z = 6 rows the
    # default survivors leave one off the decode chip (workers 5 a chip)
    assert res["host_bytes"] == 0
    assert res["mesh_bytes"] == res["blocks"] * res["mt"] ** 2 * 8
