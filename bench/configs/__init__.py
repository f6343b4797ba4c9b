"""Model configurations, one JSON file each, found by name.

A file holds the sizes the benchmark runs, which override the program's
registered configuration of the same ``arch`` key; ``reference`` names
the family's module (``reference/<family>.py``), ``reduced`` lists
every key cut from the published source (a key inside a nested group
with a dot: ``moe.n_experts``), ``assumed`` every size the source does
not give, ``departures`` where the program's model differs from the
source, ``deployment`` what one chip stands for.
"""
from __future__ import annotations

import dataclasses
import json
import typing
from pathlib import Path

from bench import found

#: keys of a configuration file that describe it and are not model sizes;
#: ``bench`` is not in the file: ``load`` sets it to the benchmark
#: directory the file was found in, where its family's files are found
META = ("name", "source", "hf_config", "arch", "reference", "reduced",
        "assumed", "departures", "deployment", "bench")


def _has_key(data: dict, dotted: str) -> bool:
    for part in dotted.split("."):
        if not isinstance(data, dict) or part not in data:
            return False
        data = data[part]
    return True


def load(name: str, bench=found.BENCH) -> dict:
    path = Path(bench) / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    data = json.loads(path.read_text())
    if data.get("name") != name:
        raise ValueError(f"{path} names itself {data.get('name')!r}")
    missing = [k for k in data.get("reduced", []) if not _has_key(data, k)]
    if missing:
        raise KeyError(f"{path}: reduced names keys it does not hold: "
                       f"{missing}")
    data["bench"] = str(bench)
    return data


def _dataclass_in(hint):
    """The dataclass a field's type names, also inside ``Optional``."""
    for t in (hint, *typing.get_args(hint)):
        if dataclasses.is_dataclass(t):
            return t
    return None


def _build(value, hint, current, where: str):
    """``value`` from a JSON file as the field of type ``hint`` takes it:
    an object becomes the field's dataclass (its keys set on
    ``current`` where that is one already), a list a tuple."""
    if isinstance(value, list):
        return tuple(_build(v, None, None, where) for v in value)
    if not isinstance(value, dict):
        return value
    cls = _dataclass_in(hint)
    if cls is None:
        raise TypeError(f"{where}: an object where the program takes "
                        f"{hint}")
    hints = typing.get_type_hints(cls)
    extra = sorted(set(value) - {f.name for f in dataclasses.fields(cls)})
    if extra:
        raise KeyError(f"{where}: keys the program has no size for: "
                       f"{extra}")
    fields = {k: _build(v, hints[k], getattr(current, k, None),
                        f"{where}.{k}") for k, v in value.items()}
    if isinstance(current, cls):
        return dataclasses.replace(current, **fields)
    return cls(**fields)


def model_config(data: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every size of the file set on it,
    nested groups as the program's dataclasses. Keys the family module
    declares ``REFERENCE_ONLY`` are left out."""
    from bench import reference
    from repro.configs import get_config
    family = reference.load(data["reference"], data.get("bench", found.BENCH))
    skip = META + tuple(getattr(family, "REFERENCE_ONLY", ()))
    base = get_config(data["arch"])
    sizes = {k: v for k, v in data.items() if k not in skip}
    return _build(sizes, type(base), base, data["name"])
