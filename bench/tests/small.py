"""Small versions of the benchmark's cells for tests on the CPU: the same
drivers, references and comparison at sizes a test run holds."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def cell(workload: str):
    """(config, traffic) of ``workload`` cut to a test size."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / cfgs[wl["config"]]["file"]).read_text())
    traffic = copy.deepcopy(json.loads(
        (ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").read_text()))
    traffic["trace_seconds"] = 0.5
    # bfloat16 rounding of the params drifts over the rounds: the control
    # needs some tens of them to leave the limits
    traffic["rounds"] = 40
    traffic["eval_every"] = min(traffic["eval_every"], 2)
    traffic["check_calls"] = 1
    if traffic["data"] == "lazy":
        traffic["n_keys"] = 2
        for k in ("population", "lazy"):
            traffic[k]["n_devices"] = 1000
        traffic["lazy"]["eval_cohort"] = 8
    else:
        traffic["synthetic"].update(n_devices=8, mean_size=20)
        traffic["fl"]["n_selected"] = 4
    traffic["fl"]["max_local_steps"] = 4
    return config, traffic
