"""Modules of the benchmark found by name, so that a later cell needs new
files only.

    plugins.module(kind, name)  ->  the module ``<kind>/<name>.py``

``kind`` is a directory beside this file: ``models`` (chosen by a
configuration's ``model.arch``), ``generators`` (chosen by a traffic mix's
``generator``) or ``metrics`` (a per-layer metric's name). A ``-`` in a
name reads as ``_`` in the file name (``feds3a-cnn`` is
``models/feds3a_cnn.py``). The interface each kind keeps is in the
docstring of that directory's ``__init__.py``. A name with no file is an
error, never a default.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent
_LOADED = {}


def module(kind, name, root=HERE):
    """The module ``<root>/<kind>/<name>.py``, loaded once per file."""
    stem = name.replace("-", "_")
    path = (Path(root) / kind / f"{stem}.py").resolve()
    if not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py")
                       if p.stem != "__init__")
        raise KeyError(f"no {kind} module {name!r} ({path}); known: {known}")
    mod = _LOADED.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return mod


def model(model_config, root=HERE):
    """The model plug-in of a configuration's ``model`` section."""
    return module("models", model_config["arch"], root)


def generator(traffic, root=HERE):
    """The generator plug-in of a traffic mix."""
    return module("generators", traffic["generator"], root)
