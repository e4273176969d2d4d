"""The benchmark of ACCL-TPU: cells named in ``BENCHMARK.json``, run by
``benchmark/run.py``. Everything that belongs to one configuration,
traffic mix or metric is a file of its own under this directory, found
by the name that ``BENCHMARK.json`` gives it (``spec.py``)."""

import importlib.util
import os

_LOADED = {}


def load_file(root, kind, name):
    """The module ``<root>/benchmark/<kind>/<name>.py`` (``root`` None:
    this checkout), loaded once per path; ``ValueError`` where there is
    no such file."""
    base = os.path.join(root, "benchmark") if root else os.path.dirname(
        os.path.abspath(__file__))
    path = os.path.join(base, kind, f"{name}.py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise ValueError(f"no {kind} file {path}")
        mod_name = f"benchmark_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
