"""``BENCHMARK.json`` keeps the benchmark's own rules: names, units, keys,
and that every cell reports set-up, another end-to-end metric and a
per-layer metric, each with a reader."""
import json
import os
import re

from chipbench.tests.conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_entries_have_only_the_contract_keys():
    spec = _spec()
    for section, keys in KEYS.items():
        for e in spec[section]:
            extra = set(e) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            assert set(e) >= keys and not extra, (section, e["name"])
            assert NAME.match(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    spec = _spec()
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        e2e = [m["name"] for m in spec["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = [m for m in spec["per_layer"]
                  if w["name"] in m["workloads"]]
        assert layers and all(m["moves"] in e2e for m in layers)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
    for c in spec["configs"]:
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
