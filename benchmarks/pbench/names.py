"""Generators and references found by name, as traffic mixes and per-layer
metrics are. `config["kind"]` (or, where the configuration has one frame, that
frame's `kind`) names the module `pbench/kinds/<kind>.py` and
`config["correctness"]["reference"]` the module `pbench/refs/<name>.py`;
a later PR adds one as a new file (protocols: the two packages' docstrings)."""

from __future__ import annotations

import importlib
import os
import pkgutil
from typing import List


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


def frames(config: dict) -> List[dict]:
    """The configuration's frames, the first of them the default: `frames`,
    a list, or `frame`, which means a list of that one."""
    return list(config["frames"]) if "frames" in config else [config["frame"]]


def kind(config: dict):
    """The configuration's generator (protocol: `pbench/kinds/__init__.py`).
    A configuration of several frames names it once, as `kind`."""
    name = config["kind"] if "kind" in config else frames(config)[0]["kind"]
    return _find("frame kind", str(name), "pbench.kinds")


def reference(config: dict):
    """The configuration's reference: `slice_part(...)` and `assemble(...)`."""
    return _find("reference", str(config["correctness"]["reference"]),
                 "pbench.refs")
