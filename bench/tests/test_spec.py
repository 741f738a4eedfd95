"""BENCHMARK.json and the data files it names keep to the benchmark's
contract: names, units, bounds, files found by name, every cell reporting
what it must."""
import json
import math
import re

import pytest

import spec

B = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# widths may never be cut
WIDTHS = ("hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
          "num_key_value_heads")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][0] == "python3" and B["command"][1].startswith(
        B["paths"][0] + "/")
    assert 1 <= B["run_seconds"] <= 51
    cells = len(B["workloads"])
    runs = 2 + 14 * 24          # a full check with the most cells allowed
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert cells <= 24


def test_names_units_and_keys():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in B[group]:
            assert set(e) == keys
            assert NAME.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for e in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["name"] not in seen
        seen.add(e["name"])
    for e in B["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in B["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert e["moves"] in {m["name"] for m in B["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in B["workloads"]])
def test_cell_files_and_reports(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        # a per-layer metric's cells report the metric it moves
        assert m["moves"] in e2e
        assert callable(spec.reader("metrics", m["name"]))
    for m in c.end_to_end:
        assert callable(spec.reader("e2e", m["name"]))
    assert c.chips in (1, 4)
    assert {"logit_gap", "tokens_checked"} <= set(c.limits)


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(conf):
    f = spec.load_json(spec.ROOT / conf["file"])
    assert conf["file"].startswith(B["paths"][0] + "/configs/")
    assert not set(conf["reduced"]) & set(WIDTHS)
    assert set(conf["reduced"]) == set(f["reduced"])
    for k in conf["reduced"]:
        assert f["reduced"][k][1] == f[k] != f["reduced"][k][0]
    fields = spec.model_fields(f)
    cfg = spec.program_config(f)
    for k, v in fields.items():
        assert getattr(cfg, k) == v, k


def test_every_workload_names_a_config_and_mix():
    confs = {c["name"] for c in B["configs"]}
    for w in B["workloads"]:
        assert w["config"] in confs
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    used = {w["config"] for w in B["workloads"]}
    assert used == confs
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, math.floor(len(B["workloads"]) / 2))
