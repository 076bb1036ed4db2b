"""BENCHMARK.json keeps to the benchmark's format: names, units and
free text in their alphabets, every entry with just its keys, and every
file a name points at present."""
import json
import re

import pytest

from bench import common

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def all_names():
    out = []
    for c in BENCH["configs"]:
        out += [c["name"], *c["reduced"]]
    for w in BENCH["workloads"]:
        out += [w["name"], w["config"], w["traffic"]]
    out += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    return out


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("name", all_names())
def test_name_alphabet(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    keys = {"name", "unit", "better", "source"}
    if metric in BENCH["end_to_end"]:
        keys |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        keys |= {"layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert text_ok(metric["layer"])
        assert metric["moves"] in E2E
        assert (common.BENCH_DIR / "metrics" / f"{metric['name']}.py"
                ).is_file()
    assert set(metric) - {"workloads"} == keys
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_unique_names():
    for section in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[section]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert text_ok(cfg["why"]) and text_ok(cfg["source"])
    assert cfg["source"].startswith("https://")
    assert cfg["file"].startswith("bench/")
    data = common.load_json(common.ROOT / cfg["file"])
    assert data["name"] == cfg["name"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert key in data["published"] and data[key] != data["published"][key]
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    for key in ("source", "deployment", "departures", "assumed"):
        assert key in data


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and text_ok(cell["why"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = common.traffic_file(cell["traffic"])
    assert mix["kind"] in ("train", "serve")
    e2e = [m["name"] for m in common.metrics_for(cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = common.metrics_for(cell["name"], "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


def test_command_and_paths():
    assert BENCH["paths"] == ["bench"]
    assert len(BENCH["command"]) <= 32
    assert all(text_ok(w) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


def test_run_seconds_fit_a_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_four_chip_share():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
