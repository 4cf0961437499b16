"""What a cell is made of, read from the benchmark's data files.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds, by those names:

- ``configs/<config>.json``: the model's sizes, as run;
- ``traffic/<mix>.json``: the parameters of one traffic mix;
- ``cells/<cell>.json``: the serving sizes of the cell and the limit of
  its output check.

A configuration picks its model family with ``"family"`` (``stack`` where
it has none); ``families/<family>/`` holds what is particular to it, found
by that name.  This module hands the program's ``ModelConfig`` and the
weights over to the family, and makes the weights on the device from the
seed.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    sizes: dict          # cells/<cell>.json


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        if w["name"] == name:
            return load_cell(name, w["config"], w["traffic"], int(w["chips"]))
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_cell(name: str, config: str, traffic: str, chips: int = 1) -> Cell:
    return Cell(name=name, chips=chips,
                config=load_json(HERE / "configs" / f"{config}.json"),
                traffic=load_json(HERE / "traffic" / f"{traffic}.json"),
                sizes=load_json(HERE / "cells" / f"{name}.json"))


def load_peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------------
# the configuration's family: its program, reference and counts
# ---------------------------------------------------------------------------

#: one directory per model family, each with ``program.py`` (the program's
#: ``ModelConfig`` and the weights), ``reference.py`` (the plain float32
#: forward pass and its float8 control) and ``counts.py`` (operations and
#: bytes from shapes)
FAMILIES = HERE / "families"


@functools.cache
def load_module(path: Path):
    """The Python file at ``path`` as a module, loaded once."""
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def family(conf: dict, part: str):
    """The module ``part`` of the configuration's family: the family that
    its ``"family"`` key names, ``stack`` where it has none."""
    path = FAMILIES / conf.get("family", "stack") / f"{part}.py"
    if not path.is_file():
        raise KeyError(f"no {path}")
    return load_module(path)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(conf, "program").model_config(conf)


def make_weights(key, conf: dict) -> dict:
    """All weights of ``conf`` in the program's layout, from ``key``."""
    return family(conf, "program").make_weights(key, conf)


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A key from a seed of any size: the low 32 bits make the key and the
    rest are folded in."""
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def weights_on_device(conf: dict, seed: int) -> dict:
    w = jax.jit(make_weights, static_argnums=1)(seed_key(seed),
                                                _Frozen(conf))
    jax.block_until_ready(w)
    # drop the generator's program, and with it any device memory the
    # runtime keeps for it, before the program under test loads its own
    jax.clear_caches()
    return w


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))
