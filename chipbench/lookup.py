"""Parts of the benchmark found by name, one Python file each.

A per-layer metric (``chipbench/metrics/<name>.py``), a deployment kind
(``chipbench/deployments/<kind>.py``) and an arrival process
(``chipbench/processes/<process>.py``) are files that a later change adds
without editing the harness; :func:`module` loads one by its name.
"""
from __future__ import annotations

import importlib.util
import os


def module(directory: str, name: str, what: str):
    """The module of ``<directory>/<name>.py``. A missing file is an error
    that names it: the harness never falls back to another part."""
    path = os.path.join(directory, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{os.path.basename(directory)}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
