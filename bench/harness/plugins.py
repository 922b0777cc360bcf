"""Load the benchmark's per-name files: ``bench/<kind>/<name>.py``.

Every part that belongs to one configuration family, one reference or
one metric lives in a file of its own, found by the name that
``BENCHMARK.json`` or a configuration file gives:

* ``bench/families/<family>.py``   - the model, its evidence patterns and
  the wire requests of a configuration's ``family``
* ``bench/reference/<reference>.py`` - the plain reference a
  configuration's ``reference`` names
* ``bench/metrics/<metric>.py``     - one reader per metric
"""
from __future__ import annotations

import importlib.util

from harness.spec import BENCH

_LOADED: dict[tuple[str, str], object] = {}


def load(kind: str, name: str):
    key = (kind, name)
    if key not in _LOADED:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file {path.name} under "
                                    f"bench/{kind}/")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]
