"""A configuration's file, and the way from it to its family.

`configs/<config>.json` holds the published `config.json` keys under their
own names and says which family it is (`"family"`, with no default behind
it). Everything the harness knows of an architecture (the mapping to the
program's `ModelConfig`, the plain reference, the bytes a resident token and
a decode step cost, what marks a decode step in a trace) is its family's
module, `families/<family>.py`; `family(raw)` finds it by that name, and
nothing outside `families/` names a field of any architecture.

A file of the benchmark is looked for under `root` first and beside this
module second (`find`): a test's temporary root holds only what it adds.
"""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def find(root: str, *parts: str) -> str:
    """The path of a file of the benchmark: under `root` where it is there,
    else beside this module."""
    path = os.path.join(root, *parts)
    return path if os.path.exists(path) else os.path.join(HERE, *parts)


def load_json(root: str, *parts: str) -> dict:
    with open(find(root, *parts)) as f:
        return json.load(f)


def load_config(name: str, root: str = HERE) -> dict:
    raw = load_json(root, "configs", f"{name}.json")
    raw["name"] = name
    return raw


@functools.lru_cache(maxsize=None)
def _module_at(path: str):
    """A family's module from a root that is no package (a test's)."""
    name = "benchmark_family_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def family(raw: dict, root: str = HERE):
    """The module of the configuration's family."""
    if "family" not in raw:
        raise KeyError(f"configuration {raw.get('name')!r} names no family "
                       f"(`\"family\"` in its file; there is no default)")
    path = find(root, "families", f"{raw['family']}.py")
    if path.startswith(HERE + os.sep):        # the benchmark's own package
        return importlib.import_module(f"benchmark.families.{raw['family']}")
    return _module_at(path)
