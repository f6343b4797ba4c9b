"""Print what a profiler trace holds, to read one by hand before
writing a reduction against it:

    python bench/tools/inspect_trace.py .bench_runs/trace/<cell>
"""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]


def main(trace_dir):
    from jax.profiler import ProfileData
    from bench import trace
    path = trace.latest_xplane(trace_dir)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            tot = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {ns / 1e6:10.3f} ms  {name[:150]}")
            if evs and plane.name.startswith("/device"):
                e = max(evs, key=lambda e: e.duration_ns)
                print(f"    longest: {e.name[:120]} stats "
                      f"{[(k, str(v)[:150]) for k, v in e.stats][:12]}")
            kern = sorted({e.name.split(" = ")[0] for e in evs
                           if "custom-call(" in e.name})
            if kern:
                print(f"    custom calls: {kern[:40]}")
    tr = trace.load(path)
    print("window_s", tr.window_s, "busy_s", trace.busy_s(tr))
    print("idle", trace.top(trace.idle_gaps(tr)))


if __name__ == "__main__":
    main(sys.argv[1])
