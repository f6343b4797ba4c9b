"""Where a traced run's device idle time went, by the program's own
spans: idle seconds by the innermost program span, how much of the idle
time inside engine steps no finer span names, the span metrics, and how
many spans of each name one engine step opens.

    python bench/tools/span_report.py .bench_runs/trace/<cell>

Prints one JSON object.
"""
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]


def report(trace_dir: str) -> dict:
    from bench import spans, trace
    tr = trace.load(trace.latest_xplane(trace_dir))
    n = spans.steps(tr)
    counts = {}
    for name, s, e in spans.program_spans(tr):
        if tr.window[0] <= s and e <= tr.window[1]:
            counts[name] = counts.get(name, 0) + 1
    return {
        "window_s": tr.window_s, "busy_s": trace.busy_s(tr), "steps": n,
        "idle_by_span_s": dict(trace.top(spans.idle_by_span(tr), 40)),
        "coverage_s": spans.coverage(tr),
        "mmu_idle_ms": spans.idle_ms_per_step(tr, ("kv.", "mmu.")),
        "engine_idle_ms": spans.idle_ms_per_step(tr, ("engine.",)),
        "vmm_mediate_us": spans.mediate_us(tr),
        "spans_per_step": {k: v / n for k, v in sorted(counts.items())}
        if n else {},
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1])), flush=True)
