"""Small versions of the benchmark's cells for the CPU tests: every
width cut, the same code paths. They live in a benchmark directory of
their own, written under a test's temporary path, so no committed cell
or configuration carries a size for tests."""
import copy
import json
import shutil

from bench import configs, harness

SIZES = {
    "internlm2-1.8b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                           d_head=16, d_ff=128, vocab=512),
    "rwkv6-1.6b": dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                       rwkv_head_dim=64, d_ff=256, vocab=512),
}


def config(name: str, **extra) -> dict:
    """Configuration ``name`` at the tests' small sizes."""
    return {**configs.load(name), **SIZES[name], **extra}


def workload(name: str) -> dict:
    wl = copy.deepcopy(harness.load_workload(name))
    wl["engine"].update(slots=4, capacity=256, chunk_tokens=64)
    tr = wl["traffic"]
    tr["prompt"].update(min=16, max=128, median=48, multiple=16)
    tr["output"].update(min=4, max=24, median=8)
    tr["arrivals"]["rate_per_s"] = 4.0
    tr["preroll_s"] = 1
    wl["trace_s"] = 1
    # at these widths logits spread about 0.16: bfloat16 runs read gaps
    # near 1e-3, the float8 control near 0.08, the planted faults 0.37
    # and more (CPU)
    wl["check"]["max_logit_gap"] = 0.02
    return wl


def bench_dir(root, cells):
    """A benchmark directory under ``root`` holding ``cells`` and their
    configurations at small sizes, and the committed metric readers."""
    shutil.copytree(harness.BENCH / "metrics", root / "metrics")
    (root / "configs").mkdir()
    (root / "workloads").mkdir()
    for cell in cells:
        wl = workload(cell)
        (root / "workloads" / f"{cell}.json").write_text(json.dumps(wl))
        (root / "configs" / f"{wl['config']}.json").write_text(
            json.dumps(config(wl["config"])))
    return root
