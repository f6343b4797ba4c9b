"""Model configurations, one JSON file each, found by name.

A file holds the sizes the benchmark runs, which override the program's
registered configuration of the same ``arch`` key; ``reduced`` lists
every key cut from the published source, ``assumed`` every size the
source does not give, ``departures`` where the program's model differs
from the source, ``deployment`` what one chip stands for.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: keys of a configuration file that describe it and are not model sizes
META = ("name", "source", "hf_config", "arch", "reference", "reduced",
        "assumed", "departures", "deployment")
#: sizes the reference and the operation counts read, which the program
#: fixes in code and takes no setting for
REFERENCE_ONLY = ("rwkv_mix_lora_rank", "rwkv_decay_lora_rank")


def load(name: str, root: Path = HERE) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    data = json.loads(path.read_text())
    if data.get("name") != name:
        raise ValueError(f"{path} names itself {data.get('name')!r}")
    return data


def model_config(data: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every size of the file set on it."""
    from repro.configs import get_config
    base = get_config(data["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    sizes = {k: v for k, v in data.items()
             if k not in META + REFERENCE_ONLY}
    extra = sorted(set(sizes) - fields)
    if extra:
        raise KeyError(f"{data['name']}: keys the program has no size "
                       f"for: {extra}")
    return dataclasses.replace(base, **sizes)

