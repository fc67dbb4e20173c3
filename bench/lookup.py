"""A cell's own code, found by name under its benchmark directory.

``BENCHMARK.json`` names a cell's configuration, traffic and metrics; the
harness builds in the arrival laws, the reference and the traffic drivers
that the existing cells use and, for any name it does not build in, loads
the file of that name:

* ``laws/<law>.py``          — ``program(law, n, seed)`` and
  ``reference(law, t, q)``, the two halves of an arrival law;
* ``references/<config>.py`` — a configuration's own reference, laid over
  ``reference.py`` (``compare.reference_for``);
* ``drivers/<kind>.py``      — ``DRIVER``, a traffic driver class;
* ``metrics/<metric>.py``    — ``read(ctx)``, a per-layer metric's reader.

So a later configuration, traffic mix or metric is added as new files, with
no edit to a file the benchmark already has.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path


def path_of(bench: Path, folder: str, name: str) -> Path:
    return Path(bench) / folder / f"{name}.py"


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{Path(path).parent.name}_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(bench: Path, folder: str, name: str):
    """``<bench>/<folder>/<name>.py`` as a module, loaded once per process;
    raises ``FileNotFoundError`` naming the path when there is no such
    file."""
    path = path_of(bench, folder, name)
    if not path.is_file():
        raise FileNotFoundError(f"bench: {name!r} is not built in, and there "
                                f"is no file {path}")
    return _load(str(path.resolve()))
