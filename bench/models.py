"""Served-model sizes read from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the accurate
model at its top level, under the key names of its published config, and
the fast model as the nested group ``fast_model``.  Each group names its
architecture with ``"arch"``: the module ``bench/archs/<arch>.py``, which
reads the group into its spec (``dims``) and holds everything else that is
specific to the kind (``bench/archs/__init__.py``).
"""
from __future__ import annotations

import importlib
import json
import re
import sys
from pathlib import Path

ARCH_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def arch(dims):
    """The architecture module that made ``dims``."""
    return sys.modules[type(dims).__module__]


def dims_of(group: dict, path, key: str):
    """The spec of one model group, by the module its ``arch`` names
    (``key`` is where that ``arch`` sits in the file at ``path``)."""
    name = group.get("arch")
    if not isinstance(name, str) or not ARCH_NAME.fullmatch(name):
        raise ValueError(f"{path}: {key} must name a module of bench/archs, not {name!r}")
    module = f"bench.archs.{name}"
    try:
        mod = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"{path}: {key} {name!r} names no module bench/archs/{name}.py") from None
    return mod.dims(group)


def load_config(path: Path) -> dict:
    """The configuration file with ``roles``: {"fast": dims, "accurate": dims}."""
    cfg = json.loads(Path(path).read_text())
    cfg["roles"] = {"fast": dims_of(cfg["fast_model"], path, "fast_model.arch"),
                    "accurate": dims_of(cfg, path, "arch")}
    return cfg
