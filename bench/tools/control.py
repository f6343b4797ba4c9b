"""Readings for a cell's limit: on several seeds in one process, the
program's widest logit gap and the float8 control's over the same
served tokens, at the cell's own size and load.

    python bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 15

Prints one JSON line per seed, with the verdict on the program and on
the control put in its place, then the largest program reading and the
smallest control reading.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    a = ap.parse_args()
    from bench import harness
    prog, ctl = [], []
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        res = harness.run_cell(args, time.perf_counter(), control=True)
        prog.append(res["checks"]["max_logit_gap"]["value"])
        ctl.append(res["control"]["checks"]["max_logit_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "failed": res["failed"],
                          "program": prog[-1], "control": ctl[-1],
                          "peak": res["device"]["memory_peak_bytes"]}),
              flush=True)
    print(json.dumps({"workload": a.workload, "program_max": max(prog),
                      "control_min": min(ctl), "program": prog,
                      "control": ctl}), flush=True)


if __name__ == "__main__":
    main()
