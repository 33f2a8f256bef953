"""The harness takes cells as data: every configuration and every mix
found by name resolves, each per-layer metric has its reader, and
BENCHMARK.json keeps the benchmark's contract."""
import itertools
import json
import re
from pathlib import Path

import pytest

from bench import cells, harness

SPEC = cells.load_spec()
BENCH = cells.BENCH
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("config,mix", list(itertools.product(CONFIGS,
                                                              MIXES)))
def test_every_config_and_mix_pair_resolves(config, mix):
    """A later cell over these files is one ``workloads`` entry (and its
    limits file): nothing else has to change."""
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "c", "source": "x",
                            "file": f"bench/configs/{config}.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "probe", "config": "c",
                              "traffic": mix, "chips": 1, "why": "x"})
    cell = cells.resolve(spec, "probe")
    harness.program_config(cell.config)
    assert cells.reference(cell.config).logits
    assert cells.flops(cell.config).decode_flops(cell.config, 8) > 0
    src = cells.generator(cell.traffic).make(cell.traffic,
                                             cell.config["vocab"], 1, 1, 2)
    sent = src.poll(3.0, 0)
    assert sent and all(len(a.prompt) + a.max_new
                        <= cell.traffic["engine"]["cache_len"]
                        for a in sent)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_reports_what_the_contract_asks(w):
    cell = cells.resolve(SPEC, w["name"])
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert cells.reader(m["name"]).read
    assert cell.limits["max_logit_gap"]["limit"] > 0
    assert cell.traffic["check_requests"] >= 1


def test_benchmark_json_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (cells.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        layers.add(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    roof = [m for m in SPEC["per_layer"] if "_roofline" in m["name"]]
    for r in roof:
        assert any("mfu" in m["name"] and m["moves"] == r["moves"]
                   and set(m["workloads"]) >= set(r["workloads"])
                   for m in SPEC["per_layer"])
