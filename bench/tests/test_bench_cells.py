"""Every cell of BENCHMARK.json resolves its files by name, and the
benchmark's data keeps to the shape the harness reads."""
import json
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


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_keeps_the_published_widths_and_depth(path):
    cfg = json.loads(path.read_text())
    model = cfg["model"]
    assert (model["hidden_size"], model["intermediate_size"]) == (2048, 8192)
    assert model["num_hidden_layers"] == 40
    assert cfg["projections"]["down"] == [8192, 2048]
    assert set(cfg["reduced"]) == set(cfg["published"])


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
