"""Tiny stand-ins for the benchmark's cells, for its CPU tests: a small
catalog in a temporary directory, laid out as the benchmark lays out its
own files."""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: tiny stand-ins for the two cells: the same families and metric files,
#: at sizes a CPU test run holds. Their limits sit between what the sound
#: program and the control read at these sizes on the CPU (see
#: ``test_chipbench_correct.py``).
TINY_LM = {
    "name": "tiny_lm", "family": "ntx_decoder", "source": "test",
    "config": {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
               "vocab_size": 256, "rms_norm_eps": 1e-6},
    "train": {"lr": 0.05, "momentum": 0.0, "init": "normal", "init_std": 0.02},
}
TINY_CNN = {
    "name": "tiny_cnn", "family": "ntx_cnn", "source": "test",
    "config": {"image": [16, 16, 3], "num_classes": 10, "layers": [
        ["conv0", "conv", {"k": 3, "cout": 8, "stride": 2, "padding": 1}],
        ["relu0", "relu"], ["pool0", "maxpool", {"window": 2, "stride": 2}],
        ["conv1", "conv", {"k": 3, "cout": 16, "stride": 1, "padding": 1}],
        ["relu1", "relu"], ["flat", "flatten"], ["fc", "fc", {"cout": 10}]]},
    "train": {"lr": 0.01, "momentum": 0.9, "init": "xavier"},
}
TINY_TOKENS = {"kind": "tokens", "batch": 2, "seq": 8, "ids": "zipf", "zipf_exponent": 1.0,
               "target": {"mul": 3, "add": 1}, "pool": 4, "loop": "closed"}
TINY_IMAGES = {"kind": "images", "batch": 4, "task": "frequency_band", "noise": 0.1,
               "pool": 4, "loop": "closed"}
TINY_LIMITS = {
    "tiny.lm": {"loss_gap.1": 1e-6, "grad_gap": 5e-6, "step_gap.median": 6e-8},
    "tiny.cnn": {"loss_gap.1": 1e-6, "grad_gap": 2.5e-7, "step_gap": 1e-5},
}


def write_catalog(root: Path) -> Path:
    """A catalog of the two tiny cells under ``root``; returns ``root``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": c["name"], "source": "test", "file": f"chipbench/configs/{c['name']}.json",
         "reduced": [], "why": "test"} for c in (TINY_LM, TINY_CNN)]
    bench["workloads"] = [
        {"name": "tiny.lm", "config": "tiny_lm", "traffic": "tiny_tokens", "chips": 1, "why": "test"},
        {"name": "tiny.cnn", "config": "tiny_cnn", "traffic": "tiny_images", "chips": 1, "why": "test"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.lm"] if "lm" in m["workloads"][0] else ["tiny.cnn"]
    d = root / "chipbench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in (TINY_LM, TINY_CNN):
        (d / "configs" / f"{c['name']}.json").write_text(json.dumps(c))
    (d / "traffic" / "tiny_tokens.json").write_text(json.dumps(TINY_TOKENS))
    (d / "traffic" / "tiny_images.json").write_text(json.dumps(TINY_IMAGES))
    for cell, limits in TINY_LIMITS.items():
        (d / "limits" / f"{cell}.json").write_text(json.dumps({"limits": limits}))
    for f in (ROOT / "chipbench" / "metrics").glob("*.py"):
        (d / "metrics" / f.name).write_text(f.read_text())
    return root
