"""Generators and references found by name, as traffic mixes and per-layer
metrics are. `config["frame"]["kind"]` names the module `pbench/kinds/<kind>.py`
and `config["correctness"]["reference"]` the module `pbench/refs/<name>.py`;
a later PR adds one as a new file (protocols: the two packages' docstrings)."""

from __future__ import annotations

import importlib
import os
import pkgutil


class UnknownName(Exception):
    pass


def _find(what: str, name: str, package: str):
    full = f"{package}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
    pkg = importlib.import_module(package)
    known = sorted(m.name for m in pkgutil.iter_modules(pkg.__path__))
    raise UnknownName(f"unknown {what} {name!r} (known: {known}; a new one is "
                      f"the file {package.replace('.', os.sep)}/{name}.py)")


def kind(config: dict):
    """The generator of the configuration's frame: `generate(config, seed,
    data_dir, plan)` and `stage_query(frame_name)`."""
    return _find("frame kind", str(config["frame"]["kind"]), "pbench.kinds")


def reference(config: dict):
    """The configuration's reference: `slice_part(...)` and `assemble(...)`."""
    return _find("reference", str(config["correctness"]["reference"]),
                 "pbench.refs")
