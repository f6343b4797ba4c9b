"""Find a cell's knee once: run its traffic at several fixed rates, one
window each, and print the end-to-end numbers and how many requests were
still queued or in flight when the window closed (a backlog that grows
with the rate marks the knee).

    python bench/tools/sweep.py --workload <cell> --rates 1,1.5,2 --seconds 20
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    from bench import harness
    base = harness.load_workload(a.workload)
    man = harness.load_manifest()
    for rate in [float(r) for r in a.rates.split(",")]:
        wl = copy.deepcopy(base)
        wl["traffic"]["arrivals"]["rate_per_s"] = rate
        args = argparse.Namespace(workload=a.workload, seed=a.seed,
                                  seconds=a.seconds, trace=0)
        res = harness.run_cell(args, time.perf_counter(), manifest=man,
                               workload=wl, check=False)
        print(json.dumps({"rate": rate, "attempted": res["attempted"],
                          "backlog": res["backlog"],
                          **{k: v["value"] for k, v in
                             res["metrics"].items()}}), flush=True)


if __name__ == "__main__":
    main()
