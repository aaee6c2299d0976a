"""The reduction from a trace to the per-layer metrics, on small traces
whose numbers are worked out by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, readers, trace as tr, traffic  # noqa: E402
from bench.harness.runner import RunView  # noqa: E402

BENCH = cells.load_benchmark()

# device 0 is busy over [0, 150] and [300, 400] of a 1000 ns window; the
# host flushes over [150, 300] and sleeps over [500, 900]
SMALL = tr.Trace(
    ops={0: [("%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p.1)", 0, 100),
             ("fusion.2", 50, 150),
             ("reduce-scatter.3", 300, 400), ("copy.9", 1200, 1300)],
         1: [("fusion.1", 0, 600), ("all-gather.1", 700, 720)]},
    modules={0: [("jit_fused(12)", 0, 160), ("jit_remainder(3)", 300, 400)],
             1: [("jit_step(5)", 0, 720)]},
    host=[("bench.window", 0, 1000), ("bench.flush", 150, 300),
          ("bench.sleep", 500, 900)])


def _view(trace, devices, completed=2, **kw):
    reqs = [traffic.Request(index=i, projection="up", operand=0, sent_s=0.0,
                            done_s=1.0) for i in range(completed)]
    fields = dict(cell=cells.resolve(BENCH, BENCH["workloads"][0]["name"]),
                  requests=reqs, trace=trace,
                  window_ns=trace.window() if trace else None,
                  devices=devices, counters={}, spans={}, blocks={}, peaks={})
    fields.update(kw)
    return RunView(**fields)


def test_union_and_gaps():
    assert tr.union(SMALL.ops[0]) == [(0, 150), (300, 400), (1200, 1300)]
    assert tr.busy_ns(SMALL, [0], 0, 1000) == 250
    assert tr.busy_ns(SMALL, [0, 1], 0, 1000) == (250 + 620) / 2
    assert tr.gaps(SMALL, 0, 0, 1000) == [(150, 300), (400, 1000)]


def test_idle_share_is_mean_over_chips():
    assert readers.idle_share(_view(SMALL, [0])) == pytest.approx(75.0)
    assert readers.idle_share(_view(SMALL, [0, 1])) == pytest.approx(
        100 * (1 - 435 / 1000))


def test_stage_time_per_product():
    # device 0: jit_fused 160 ns (jit_remainder is not a stage program);
    # device 1: jit_step 720 ns -> the busiest chip sets it
    assert readers.stage_ms_per_product(_view(SMALL, [0])) == pytest.approx(80e-6)
    assert readers.stage_ms_per_product(_view(SMALL, [0, 1])) == pytest.approx(360e-6)


def test_breakdown():
    assert tr.top_ops(SMALL, [0], 0, 1000) == [
        ["jit_fused:fusion", 200e-9],                      # summed, not merged
        ["jit_remainder:reduce-scatter", 100e-9]]
    assert tr.idle_by_host(SMALL, 0, 0, 1000) == [
        ["bench.sleep", 400e-9], ["host.other", 200e-9], ["bench.flush", 150e-9]]


def test_nothing_to_read_gives_no_number():
    assert readers.idle_share(_view(None, [0])) is None
    empty = tr.Trace(ops={}, modules={}, host=[("bench.window", 0, 10)])
    assert readers.idle_share(_view(empty, [0])) is None
    assert readers.stage_ms_per_product(_view(empty, [0])) is None
    assert readers.stage_ms_per_product(_view(SMALL, [1], completed=0)) is None
    roofline = cells.metric_reader("field_gemm_roofline")
    assert roofline(_view(SMALL, [0])) is None              # no peaks known


def test_roofline_share_from_blocks():
    peaks = cells.device_peaks("TPU v5 lite")
    view = _view(SMALL, [0], blocks={(17, 2, 2, 2, 2048): 4}, peaks=peaks)
    least, bound = view.least_time_per_product()
    assert bound == "compute"
    share = cells.metric_reader("field_gemm_roofline")(view)
    assert share == pytest.approx(100 * least * 1e3 / 80e-6)
    assert cells.metric_reader("field_gemm_roofline.sharded4")(view) == share


def test_collective_time_per_product_on_the_busiest_chip():
    # device 0: reduce-scatter 100 ns; device 1: all-gather 20 ns
    read = cells.metric_reader("sharded.collective_ms")
    assert read(_view(SMALL, [0, 1])) == pytest.approx(100e-6 / 2)
    assert read(_view(SMALL, [1, 0], completed=4)) == pytest.approx(100e-6 / 4)
    assert read(_view(SMALL, [0])) is None                  # one chip
    quiet = tr.Trace(ops={0: [("fusion.1", 0, 10)], 1: [("copy.2", 0, 10)]},
                     modules={}, host=[("bench.window", 0, 10)])
    assert read(_view(quiet, [0, 1])) is None               # none in the trace


ROUTED_CELL = "v2lite-experts-local.prefill-routed"


def _routed_view(trace, loads, blocks, **kw):
    """Two completed routed requests of the routed cell, gate then down,
    each with the held loads ``loads``."""
    cell = cells.resolve(BENCH, ROUTED_CELL)
    reqs = [traffic.RoutedRequest(index=i, projection=p, operand=0,
                                  sent_s=0.0, done_s=1.0,
                                  tokens=tuple(range(n) for n in loads))
            for i, p in enumerate(("expert_gate", "expert_down"))]
    return _view(trace, [0], cell=cell, requests=reqs, blocks=blocks, **kw)


def test_routed_pad_share_from_the_block_sides():
    # a chunk of the cell's routing: each held expert's product tiles to
    # 48 m = 256 blocks a projection
    loads = [199, 197, 201, 197, 182, 182, 191, 197]
    blocks = {(17, 2, 2, 2, 256): 2 * 8 * 48}
    view = _routed_view(SMALL, loads, blocks)
    work = 2 * sum(loads) * 2048 * 1408
    volume = 768 * 256 ** 3
    assert readers.routed_macs(view) == work
    share = cells.metric_reader("routed.pad_share")(view)
    assert share == pytest.approx(100 * (1 - work / volume))
    assert 30.5 < share < 31
    assert cells.metric_reader("routed.pad_share")(
        _routed_view(SMALL, loads, {})) is None            # no blocks
    # the dense cells' requests carry no routing
    assert readers.routed_macs(_view(SMALL, [0])) == 0


def test_step_mfu_over_the_window_and_the_peak():
    peaks = cells.device_peaks("TPU v5 lite")
    view = _routed_view(SMALL, [4, 2], {(17, 2, 2, 2, 256): 4}, peaks=peaks)
    read = cells.metric_reader("step_mfu.routed")
    ops = 2 * (6 * 2048 * 1408 + 6 * 1408 * 2048) * 16
    assert read(view) == pytest.approx(100 * ops / (1000e-9 * 393e12))
    assert read(_routed_view(None, [4, 2], {}, peaks=peaks)) is None
    assert read(_routed_view(SMALL, [4, 2], {})) is None   # no peaks known
