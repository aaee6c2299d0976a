"""Every cell of BENCHMARK.json resolves its files by name, and the
benchmark's data keeps to the shape the harness reads."""
import ast
import json
import operator
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import cells  # noqa: E402

BENCH = cells.load_benchmark()
NAMES = [w["name"] for w in BENCH["workloads"]]
#: every configuration on disk, named in BENCHMARK.json or kept for later
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("workload", NAMES)
def test_cell_resolves_config_traffic_and_metrics(workload):
    cell = cells.resolve(BENCH, workload)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == workload)
    assert set(cell.traffic["projections"]) <= set(cell.config["projections"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in names
    if cell.traffic["kind"] == "open":
        assert cell.traffic["rate_rps"] > 0
    if cell.traffic["kind"] == "routed":
        from bench.harness import traffic

        routings = traffic.chunk_routings(cell.traffic, cell.config)
        assert len(routings) == cell.traffic["operand_pool"]
        assert {len(c) for c in routings} == {
            cell.config["model"]["n_routed_experts"]}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        here = cfg["model"][key] if key in cfg["model"] else cfg[key]
        assert here < cfg["published"][key]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_spec_gives_the_stated_workers(path):
    from repro.mpc import MPCSpec
    from repro.mpc.field import Field

    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    sp = cfg["spec"]
    spec = MPCSpec(s=sp["s"], t=sp["t"], z=sp["z"], lam=sp["lam"],
                   scheme=sp["scheme"], field=Field(sp["p"], sp["frac_bits"]))
    assert spec.n_workers == cfg["n_workers"]


#: what a side of ``projection_widths`` may join its top-level keys with
WIDTH_OPS = {ast.Mult: operator.mul, ast.FloorDiv: operator.floordiv,
             ast.Add: operator.add}


def _width(expr: str, cfg: dict) -> int:
    """A side of a projection as ``expr`` gives it: the configuration's
    top-level keys and whole numbers joined by ``*``, ``//`` and ``+``."""
    def value(node):
        if isinstance(node, ast.Name):
            return cfg[node.id]
        if isinstance(node, ast.Constant) and type(node.value) is int:
            return node.value
        if isinstance(node, ast.BinOp) and type(node.op) in WIDTH_OPS:
            return WIDTH_OPS[type(node.op)](value(node.left), value(node.right))
        raise ValueError(f"{expr!r}: only top-level keys, *, // and +")

    return value(ast.parse(expr, mode="eval").body)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_keeps_the_published_widths_and_depth(path):
    """The model's published ``config.json`` keys sit at the file's top
    level, a cut at the value run and its published value in
    ``published``; the ``model`` group that the harness reads and every
    projection's ``[K, F]`` (``projection_widths``, each side a formula of
    top-level keys) agree with them."""
    cfg = json.loads(path.read_text())
    assert {k: cfg[k] for k in cfg["model"]} == cfg["model"]
    assert set(cfg["projection_widths"]) == set(cfg["projections"])
    for name, sides in cfg["projection_widths"].items():
        assert [_width(s, cfg) for s in sides] == cfg["projections"][name]
    assert set(cfg["reduced"]) == set(cfg["published"])
    for key in cfg["reduced"]:
        assert cfg[key] < cfg["published"][key]


def test_a_width_formula_reads_only_top_level_keys():
    cfg = {"hidden_size": 2048, "num_attention_heads": 32,
           "num_key_value_heads": 8}
    assert _width("num_key_value_heads * (hidden_size // num_attention_heads)",
                  cfg) == 512
    for bad in ("hidden_size - 1", "hidden_size ** 2", "len(hidden_size)"):
        with pytest.raises(ValueError):
            _width(bad, cfg)
    with pytest.raises(KeyError):
        _width("head_dim", cfg)


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_mix_file_has_only_keys_the_generator_reads(path):
    from bench.harness import traffic

    mix = json.loads(path.read_text())
    assert set(mix) <= traffic.KEYS[mix["kind"]]


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "cells").glob("*.json")),
                         ids=lambda p: p.stem)
def test_cell_file_completes_its_mix(path):
    """``<config>.<mix>.json`` sets what its mix leaves to the cell."""
    from bench.harness import traffic

    mix = path.stem.split(".", 1)[1]
    merged = {**cells.load_json(ROOT / "bench" / "traffic" / f"{mix}.json"),
              **cells.load_json(path)}
    traffic.validate(merged)


def test_every_metric_file_is_named_in_the_benchmark():
    on_disk = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert on_disk == {m["name"] for m in BENCH["per_layer"]}


def test_unknown_device_kind_is_an_error():
    assert cells.device_peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        cells.device_peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(ValueError):
        cells.resolve(BENCH, "no-such-cell")
