"""The plain reference a configuration's answers are held to.

A configuration file (``configs/<config>.json``) may carry
``"reference": "<name>"``: its answers are then checked, and its float32
control computed, by ``references/<name>.py``.  Without the key they are by
``reference.py`` beside this package.  A name that has no file is an error,
never the default.

A reference module is loaded by path and imports nothing of the program
(``repro``).  It provides what ``check.py`` calls:

* ``Graph(table)``: the configuration's ``graph`` table; ``.edges`` lists
  its edges in genome-bit order (edge ``i`` is bit ``i`` of a mask);
* ``machine_for(accelerator, machines, energy_pj, dtype)``: the machine an
  accelerator spec names, from the configuration's ``machines`` and
  ``energy_pj``; every non-integer quantity in ``dtype``, where ``float``
  is the stated float64 and ``numpy.float32`` gives the control;
* ``Reference(graph, machine)`` with ``.schedule(mask)`` (None when the
  genome is illegal), ``.baseline()`` (the layer-by-layer schedule) and
  ``.fitness(mask)`` (EDP fitness, 0 when illegal).  A schedule is a dict
  with the cost fields ``check._COST_FLOATS`` and ``check._COST_INTS`` and
  ``groups``: one dict per fused group with ``members`` (layer names,
  sorted) and the fields ``check._GROUP_FLOATS`` and ``check._GROUP_INTS``.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path
from types import ModuleType
from typing import Dict

HERE = Path(__file__).resolve().parent
DEFAULT = HERE.parent / "reference.py"
#: what a reference module must define
CONTRACT = ("Graph", "Reference", "machine_for")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownReference(LookupError):
    """A configuration names a reference that has no module."""


def path_for(config: Dict) -> Path:
    """The file of the reference ``config`` names, or the default."""
    name = config.get("reference")
    if name is None:
        return DEFAULT
    if isinstance(name, str) and _NAME.match(name):
        path = HERE / f"{name}.py"
        if path.is_file():
            return path
    raise UnknownReference(
        f"configuration {config.get('name')!r} names reference {name!r}; "
        f"no module {name}.py under {HERE}")


def load(config: Dict) -> ModuleType:
    """The reference module of ``config``, loaded from its file."""
    path = path_for(config)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [k for k in CONTRACT if not hasattr(mod, k)]
    if missing:
        raise UnknownReference(f"{path} lacks {', '.join(missing)}")
    return mod
