"""Shared set-up of the benchmark's own tests (``pytest bench/tests``):
the benchmark's modules and the program's ``src`` on the path, and small
runs of a cell on the CPU."""
from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

#: A mix small enough for the Pallas interpreter: 8 shards x 16 streams,
#: 8 window phases, 4-sample packets.
TINY = {"name": "tiny", "generator": "staggered", "loop": "closed", "streams": 128, "window_phases": 8,
        "packet_samples": 4, "tick_hz": 50.0, "pool_windows": 64,
        "pool_split": "test", "check_streams": 32}

#: On the CPU the interpreted Pallas step differs from the reference by
#: the interpreter's fused multiply-adds (a few ulp of a logit); the chip's
#: compiled step is held to 0.  Runs here use this limit instead.
CPU_GAP_LIMIT = 1e-6


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def tiny_bench(tmp_path, config: str, *, mix: dict | None = None, search=()):
    """A Bench over the repository's BENCHMARK.json with one extra cell
    ``tiny.<config>`` on the tiny mix, whose configuration is a copy of
    ``config`` held to CPU_GAP_LIMIT."""
    import harness
    spec = load_json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == config)
    cfg = load_json(entry["file"])
    cfg["check"] = dict(cfg["check"], logit_max_abs_gap=CPU_GAP_LIMIT)
    cfg_path = tmp_path / f"{config}.json"
    cfg_path.write_text(json.dumps(cfg))
    spec = copy.deepcopy(spec)
    spec["configs"].append(dict(entry, name="tiny-" + config, file=str(cfg_path)))
    traffic = tmp_path / "traffic"
    traffic.mkdir(exist_ok=True)
    m = dict(mix or TINY)
    (traffic / f"{m['name']}.json").write_text(json.dumps(m))
    spec["workloads"].append({"name": "tiny." + config, "config": "tiny-" + config,
                              "traffic": m["name"], "chips": 1, "why": "test"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("tiny." + config)
    return harness.Bench(ROOT, spec=spec, search=[str(tmp_path), *search])


def run_tiny(bench, config: str, *, trace: bool = False, bits=None, seed: int = 3):
    return bench.run("tiny." + config, seed, 1.0, trace, t_start=time.perf_counter(),
                     require_chip=False, bits=bits)


@pytest.fixture
def configs():
    return [c["name"] for c in load_json("BENCHMARK.json")["configs"]]
