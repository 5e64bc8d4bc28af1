"""A benchmark root of a tiny cell, built in a temporary directory from
files only: olmo-1b's layer pattern at 128 wide, served on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRAFFIC = {
    "loop": "closed", "clients": 4, "think_s": 0.0, "max_batch": 4,
    "prompt": {"law": "lognormal", "median": 12, "sigma": 0.5,
               "ladder": [8, 16]},
    "output": {"law": "lognormal", "median": 8, "sigma": 0.5,
               "min": 4, "max": 16},
    "pool": 64, "preroll_s": 0.3}
#: at 128 wide on the CPU sound runs read gaps of 0 to 0.03 and the fp8
#: control 0.2 to 0.5
LIMIT = 0.1
#: the fp8 control passes the limit on about 4 requests in 10, so a sample
#: of 32 requests misses it on none; one of 7 did on about 1 run in 30
SAMPLE = {"sample_tokens": 256, "sample_requests": 32}


def make_root(root: Path) -> Path:
    """BENCHMARK.json and chipbench/ files of two tiny cells under
    ``root``: ``tiny.closed`` and ``tiny.open``."""
    cb = root / "chipbench"
    for d in ("configs", "traffic", "cells"):
        (cb / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / "chipbench" / "metrics", cb / "metrics",
                    dirs_exist_ok=True)
    shutil.copy(REPO / "chipbench" / "configs" / "olmo1b.py",
                cb / "configs" / "tiny.py")
    cfg = json.loads((REPO / "chipbench/configs/olmo1b.json").read_text())
    cfg.update(name="tiny", max_len=64)
    cfg["model"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=256, vocab_size=512,
                        attn_impl="dense")
    (cb / "configs/tiny.json").write_text(json.dumps(cfg))
    (cb / "traffic/closed.json").write_text(json.dumps(TRAFFIC))
    (cb / "traffic/open.json").write_text(json.dumps(dict(
        TRAFFIC, loop="open", rate_per_s=20.0,
        arrival={"law": "gamma", "shape": 0.25})))
    for c in ("tiny.closed", "tiny.open"):
        (cb / f"cells/{c}.json").write_text(json.dumps(
            dict(SAMPLE, max_logit_gap=LIMIT)))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="tiny",
                             file="chipbench/configs/tiny.json")]
    bench["workloads"] = [
        {"name": "tiny.closed", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "tiny closed loop"},
        {"name": "tiny.open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "tiny bursty open loop"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
