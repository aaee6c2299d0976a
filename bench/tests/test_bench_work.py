"""The field-MAC counts of bench/harness/work.py against the GEMMs the
planner's stage programs really issue, for the spec of every config."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, work  # noqa: E402

#: the spec of every configuration on disk, admitted in a cell or not
SPECS = sorted({tuple(json.loads(p.read_text())["spec"].items())
                for p in (ROOT / "bench" / "configs").glob("*.json")})


def _counted_macs(monkeypatch, sp: dict, m: int) -> int:
    """Field multiply-adds of one ``fused`` block, counted from the shapes
    every ``field_matmul`` call of the plan's stage programs receives."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.mpc import planner
    from repro.mpc.field import Field

    total = []
    real = planner.field_matmul

    def counting(x, y, *, p):
        *batch, rows, k = x.shape
        total.append(int(np.prod(batch, dtype=np.int64)) * rows * k * y.shape[-1])
        return real(x, y, p=p)

    monkeypatch.setattr(planner, "field_matmul", counting)
    plan = planner.build_plan(sp["scheme"], sp["s"], sp["t"], sp["z"], sp["lam"],
                              Field(sp["p"]), m)
    stages = planner._build_stages(plan)
    a = jax.ShapeDtypeStruct((m, m), jnp.int64)
    jax.eval_shape(stages.fused, a, a, jax.random.PRNGKey(0))
    return sum(total), plan.n_workers


def test_every_deployment_spec_is_counted():
    """s = t = 2, z = 2 (local and sharded4) and s = 2, t = 4, z = 4."""
    assert {(dict(sp)["t"], dict(sp)["z"]) for sp in SPECS} == {(2, 2), (4, 4)}


@pytest.mark.parametrize("spec", SPECS, ids=str)
@pytest.mark.parametrize("m", [8, 16])
def test_work_counts_match_the_stage_programs(monkeypatch, spec, m):
    sp = dict(spec)
    counted, n = _counted_macs(monkeypatch, sp, m)
    model = work.block_field_macs(n, sp["s"], sp["t"], sp["z"], m)
    assert sum(model.values()) == counted


def test_least_time_of_a_granite_block():
    """s = t = 2, z = 2, N = 17, m = 2048 on one v5e: about 1.9e10 field
    multiply-adds, so the int8 compute bound (1.5 ms) sets the floor."""
    macs = work.block_field_macs(17, 2, 2, 2, 2048)
    assert sum(macs.values()) == pytest.approx(1.88e10, rel=0.01)
    assert macs["worker"] == 17 * 1024 * 1024 * 1024
    peaks = cells.device_peaks("TPU v5 lite")
    sec, bound = work.block_least_time(17, 2, 2, 2, 2048, peaks)
    assert bound == "compute"
    assert sec == pytest.approx(2 * sum(macs.values()) * 16 / 393e12)
    sec4, _ = work.block_least_time(17, 2, 2, 2, 2048, peaks, chips=4)
    assert sec4 == pytest.approx(sec / 4)


def test_memory_bound_takes_over_for_thin_blocks():
    peaks = cells.device_peaks("TPU v5 lite")
    _, bound = work.block_least_time(56, 2, 4, 4, 8, peaks)
    assert bound == "memory"
