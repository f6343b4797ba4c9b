"""Modules of the benchmark found by name: ``<bench>/<kind>/<name>.py``,
loaded from the file of the benchmark directory given, so that a
directory of a test's own can hold files of its own. Each file is
loaded once per process."""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def _load(path: str):
    stem = re.sub(r"\W", "_", f"{Path(path).parent.name}_{Path(path).stem}")
    spec = importlib.util.spec_from_file_location(f"bench_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(kind: str, name: str, bench=BENCH):
    """The module ``<bench>/<kind>/<name>.py`` (names may hold dots)."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    return _load(str(path.resolve()))
