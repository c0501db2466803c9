"""BENCHMARK.json against its format's rules, and every file it names."""
import importlib
import json
import re

import pytest
from _paths import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names():
    out = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out += [(key, e["name"]) for e in BENCH[key]]
    out += [("config", w["config"]) for w in BENCH["workloads"]]
    out += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    out += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_names_use_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_units_and_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in BENCH["per_layer"]:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
        importlib.import_module(f"chipbench.metrics.{metric['name']}").read  # noqa: B018
    else:
        assert 0.01 <= metric["bound"] <= 0.25


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_names_existing_files(cell):
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert cell["config"] in configs
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    importlib.import_module(f"chipbench.configs.{cfg['reference']}")
    tr = json.loads((ROOT / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    importlib.import_module(f"chipbench.entries.{tr['entry']}")
    limits = json.loads((ROOT / "chipbench" / "workloads" / f"{cell['name']}.json").read_text())
    assert limits["limits"]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_every_config_is_used_and_unreduced_keys_match():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]


def test_command_and_paths():
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
