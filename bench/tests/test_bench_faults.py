"""The comparison that decides ``correct`` fails when it should.

Each test drives the rest of a run (warm-up, the window, the check) on
the CPU at a small size, without the harness's look for a chip: sound
runs come out correct; the control (the reference one precision lower in
the program's place) and each fault a cell can have, planted in the timed
path, come out not correct.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells, runner  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
TINY = {
    "local.prefill": ("tiny.age-s2t2z2.local", "prefill-mlp"),
    "local.decode": ("tiny.age-s2t2z2.local", "decode-poisson"),
    "batched.decode": ("tiny.age-s2t4z4.batched", "decode-poisson"),
    "sharded.prefill": ("tiny.age-s2t2z2.sharded4", "prefill-mlp"),
    "local.routed": ("tiny-moe.age-s2t2z2.local", "prefill-routed"),
}


def tiny_cell(name: str) -> cells.Cell:
    config, traffic = TINY[name]
    bench = {"workloads": [{"name": name, "config": config,
                            "traffic": traffic, "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    return cells.resolve(bench, name, DATA)


@pytest.fixture(scope="module")
def compiles():
    """The window's compile counter; ``active`` only inside the window."""
    return runner.CompileCounter()


def run(name: str, compiles, server_factory=None) -> dict:
    return runner.run_cell(tiny_cell(name), 2**32 + 17, 0.3, False,
                           t_start=time.perf_counter(), compiles=compiles,
                           server_factory=server_factory, require_tpu=False)


@pytest.mark.parametrize("name", ["local.prefill", "local.decode",
                                  "batched.decode", "local.routed"])
def test_sound_run_is_correct(compiles, name):
    res = run(name, compiles)
    assert res["correct"], res["checks"]
    assert res["checks"]["fixed_point_gap"]["value"] == 0
    assert res["window"]["compiles"] == 0


@pytest.mark.parametrize("name", ["local.prefill", "local.decode",
                                  "local.routed"])
def test_control_is_not_correct(compiles, name):
    from bench.harness.server import ControlServer

    res = run(name, compiles, lambda: ControlServer(tiny_cell(name).config))
    assert not res["correct"]
    assert res["checks"]["fixed_point_gap"]["value"] > 0


@pytest.mark.parametrize("name,backend", [("local.prefill", "LocalBackend"),
                                          ("batched.decode", "BatchedBackend"),
                                          ("local.routed", "LocalBackend")])
def test_altered_answer_is_not_correct(compiles, monkeypatch, name, backend):
    from repro.mpc import backends

    cls = getattr(backends, backend)
    real = cls.run_blocks

    def altered(self, ops):
        outs = real(self, ops)
        outs[-1] = outs[-1].at[0, 0].add(1)   # one residue off where made
        return outs

    monkeypatch.setattr(cls, "run_blocks", altered)
    res = run(name, compiles)
    assert not res["correct"]
    assert res["checks"]["fixed_point_gap"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(compiles, monkeypatch):
    from repro.mpc import backends
    from repro.mpc.api import BlockFailure

    real = backends.BatchedBackend.run_blocks

    def half(self, ops):
        outs = real(self, ops)
        if not compiles.active:        # the warm-up runs sound
            return outs
        return [o if i % 2 else BlockFailure("left out")
                for i, o in enumerate(outs)]

    monkeypatch.setattr(backends.BatchedBackend, "run_blocks", half)
    res = run("batched.decode", compiles)
    assert not res["correct"]
    assert res["checks"]["missing"]["value"] > 0


def test_expert_products_left_out_are_not_correct(compiles, monkeypatch):
    """Half of a routed flush's blocks fail in the window: the requests
    they belong to never complete."""
    from repro.mpc import backends
    from repro.mpc.api import BlockFailure

    real = backends.LocalBackend.run_blocks

    def half(self, ops):
        outs = real(self, ops)
        if not compiles.active:        # the warm-up runs sound
            return outs
        return [o if i % 2 else BlockFailure("left out")
                for i, o in enumerate(outs)]

    monkeypatch.setattr(backends.LocalBackend, "run_blocks", half)
    res = run("local.routed", compiles)
    assert not res["correct"]
    assert res["checks"]["missing"]["value"] > 0


def test_routed_check_compares_every_expert(compiles):
    """A sampled routed request is compared product by product, one a
    held expert, on the rows routed to it."""
    held = []
    res = runner.run_cell(tiny_cell("local.routed"), 2**33 + 1, 0.3, False,
                          t_start=time.perf_counter(), compiles=compiles,
                          require_tpu=False, on_requests=held.extend)
    kept = [r for r in held if r.result is not None]
    assert res["correct"] and res["window"]["compiles"] == 0
    assert res["window"]["compared"] == sum(len(r.tokens) for r in kept) > 0
    for r in kept:
        assert [y.shape[0] for y in r.result] == [len(t) for t in r.tokens]


SHARDED = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax
from test_bench_faults import tiny_cell
from bench.harness import runner

cell = tiny_cell("sharded.prefill")
cell = type(cell)(**{{**cell.__dict__, "chips": 4}})
cc = runner.CompileCounter()

def once():
    return runner.run_cell(cell, 5, 0.3, False, t_start=time.perf_counter(),
                           compiles=cc, require_tpu=False)

sound = once()

def no_exchange(x, axis, *, scatter_dimension=0, tiled=False, **kw):
    # each chip keeps its own workers' contribution to its chunk
    chunk = x.shape[0] // 4
    me = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(x, me * chunk, chunk, 0)

jax.lax.psum_scatter = no_exchange
faulty = once()
print(json.dumps({{"sound": sound["correct"], "faulty": faulty["correct"],
                  "faulty_gap": faulty["checks"]["fixed_point_gap"]["value"]}}))
"""


def test_exchange_between_chips_left_out_is_not_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = SHARDED.format(root=str(ROOT), src=str(ROOT / "src"),
                          tests=str(Path(__file__).parent))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"] is True
    assert res["faulty"] is False and res["faulty_gap"] > 0
