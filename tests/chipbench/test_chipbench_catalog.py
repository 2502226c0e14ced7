"""Cells, configurations, mixes and metrics are found by name from files,
with no edit of the harness."""

from __future__ import annotations

import json

from chipbench.catalog import Catalog
from chipbench.trace_reduce import RunView

from chipbench_tiny import write_catalog


def test_files_added_in_a_directory_are_found_by_name(tmp_path):
    root = write_catalog(tmp_path)
    d = root / "chipbench"
    # a new configuration, mix and per-layer metric, added as files only
    cfg = json.loads((d / "configs" / "tiny_lm.json").read_text())
    cfg["name"] = "wider_lm"
    cfg["config"]["hidden_size"] = 128
    (d / "configs" / "wider_lm.json").write_text(json.dumps(cfg))
    (d / "traffic" / "longer.json").write_text(json.dumps(
        {"kind": "tokens", "batch": 1, "seq": 16, "ids": "zipf", "zipf_exponent": 1.0,
         "target": {"mul": 3, "add": 1}, "pool": 2}))
    (d / "metrics" / "steps_seen.x.py").write_text("def read(run):\n    return run.steps\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "wider_lm", "source": "test",
                             "file": "chipbench/configs/wider_lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wider.long", "config": "wider_lm",
                               "traffic": "longer", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_seen.x", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "test",
                               "moves": "tokens_per_s", "workloads": ["wider.long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cat = Catalog(root)
    cell = cat.cell("wider.long")
    assert cat.config(cell["config"])["config"]["hidden_size"] == 128
    assert cat.traffic(cell["traffic"])["seq"] == 16
    assert [m["name"] for m in cat.per_layer("wider.long")] == ["steps_seen.x"]
    assert [m["name"] for m in cat.end_to_end("wider.long")] == ["setup_s"]
    view = RunView(summary=None, steps=7, graph=None, cfg={}, mix={}, family=None,
                   kind="TPU v5 lite", encode_s=[])
    assert cat.reader("steps_seen.x")(view) == 7
    # and the family builds its graph from the new file's sizes
    family = cat.family(cat.config("wider_lm")["family"])
    graph = family.build(cat.config("wider_lm"), cat.traffic("longer"))
    assert graph.param_shapes()["w_emb"] == (256, 128)


def test_readers_give_nothing_without_a_device_trace(tiny_catalog):
    view = RunView(summary=None, steps=3, graph=None, cfg={}, mix={}, family=None,
                   kind="TPU v5 lite", encode_s=[])
    for m in tiny_catalog.bench["per_layer"]:
        if m["source"] == "device_trace":
            assert tiny_catalog.reader(m["name"])(view) is None, m["name"]
