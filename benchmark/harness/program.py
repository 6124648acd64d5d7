"""Builds the program's own configuration object from a configuration file.

A ``configs/<name>.json`` holds the published sizes under their published
keys, and a ``program`` group that says which class of the package takes
them and under which argument names, so each size is written once."""

from __future__ import annotations

import importlib
from typing import Any, Dict


def program_config(config: Dict[str, Any]):
    prog = config["program"]
    module = importlib.import_module(prog["config_module"])
    cls = getattr(module, prog["config_class"])
    kwargs = {arg: config[key] for arg, key in prog["args_from_keys"].items()}
    kwargs.update(prog.get("args", {}))
    return cls(**kwargs)


def reference_module(config: Dict[str, Any]):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def shape_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the FLOP and byte functions need, by generic name."""
    return {name: config[key] if isinstance(key, str) else key
            for name, key in config["shape"].items()}
