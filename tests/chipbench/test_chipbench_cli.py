"""The command as the benchmark's contract states it: it refuses to run
off a TPU, and BENCHMARK.json names only files and readers that exist."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_the_command_exits_non_zero_and_prints_no_result_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "lm_qwen05b.b4s128",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_json_names_what_exists():
    from chipbench.catalog import Catalog

    cat = Catalog()
    b = cat.bench
    names = [x["name"] for x in b["configs"] + b["workloads"]
             + b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert cat.config(c["name"])["name"] == c["name"]
    for w in b["workloads"]:
        cfg = cat.config(w["config"])
        cat.family(cfg["family"])
        cat.traffic(w["traffic"])
        assert cat.limits(w["name"]), f"no limits for {w['name']}"
        e2e = {m["name"] for m in cat.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cat.per_layer(w["name"])
    for m in b["per_layer"]:
        assert callable(cat.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert b["command"][1] == "chipbench/run.py"
    assert json.loads((ROOT / "BENCHMARK.json").read_text()) == b
