"""BENCHMARK.json against the benchmark's contract, cells made of files
only, and the run command's refusal of a host without a TPU."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

import chipbench_testcell
from chipbench import harness

REPO = chipbench_testcell.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in END_TO_END
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in END_TO_END
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_files(entry):
    assert NAME.match(entry["name"])
    for text in ("why", "layer", "source"):
        if text in entry:
            assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text]
    if "unit" in entry:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert (REPO / "chipbench" / "metrics" / f"{entry['name']}.py").exists()
    if "file" in entry:
        assert (REPO / entry["file"]).exists()
        assert (REPO / entry["file"]).with_suffix(".py").exists()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_reports(cell):
    c = harness.load_cell(REPO, cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names
    assert c.chips == 1
    assert c.limits["max_logit_gap"] > 0
    # the ladder and the outputs fit the configuration's max_len
    t = c.traffic
    assert max(t.ladder) + t.output["max"] < c.config["max_len"]
    harness.model_config(c.config)


def test_a_cell_of_files_only_loads(tmp_path):
    root = chipbench_testcell.make_root(tmp_path)
    c = harness.load_cell(root, "tiny.open")
    assert c.traffic.loop == "open" and c.config["name"] == "tiny"
    assert hasattr(c.reference, "forward")
    cfg = harness.model_config(c.config)
    assert cfg.d_model == 128 and cfg.n_layers == 2


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_engine_seed_uses_high_bits():
    assert harness.engine_seed(5) != harness.engine_seed(5 + 2**33)
    assert 0 <= harness.engine_seed(2**40 + 1) < 2**31
