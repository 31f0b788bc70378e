"""The plain reference a configuration names, through the loader: no
reference imports the program, an unknown name fails before set-up, a
configuration without the key gets the default, and the check and its
control use the module the configuration names and no other.  (Agreement,
the float32 control and the planted faults run for every configuration in
``test_chipbench_parts.py`` and ``test_chipbench_phases.py``.)"""
from __future__ import annotations

import ast
import json
import shutil

import pytest
from test_chipbench_phases import (CONFIGS, config_of, jax_engine,  # noqa: F401
                                   tiny, window)

from chipbench import check, harness, references

def _reference_files():
    return [harness.HERE / "reference.py",
            *sorted(references.HERE.glob("*.py"))]


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
        elif (isinstance(node, ast.Call) and node.args
              and isinstance(node.args[0], ast.Constant)
              and getattr(node.func, "attr", getattr(node.func, "id", ""))
              in ("import_module", "__import__")):
            yield str(node.args[0].value)


@pytest.mark.parametrize("path", _reference_files(), ids=lambda p: p.name)
def test_no_reference_imports_the_program(path):
    names = list(_imports(ast.parse(path.read_text())))
    assert not [n for n in names if n == "repro" or n.startswith("repro.")]


@pytest.mark.parametrize("name", ["no_such_reference", "../reference", 7])
def test_missing_reference_fails_in_load_parts(tmp_path, monkeypatch, name):
    for part in ("configs", "mixes", "limits"):
        shutil.copytree(harness.HERE / part, tmp_path / part)
    c = config_of("mobilenet_v3-simba")
    c["reference"] = name
    (tmp_path / "configs" / "mobilenet_v3-simba.json").write_text(
        json.dumps(c))
    monkeypatch.setattr(harness, "HERE", tmp_path)
    cell = {"name": "mobilenet_v3-simba.search",
            "config": "mobilenet_v3-simba", "traffic": "search", "chips": 1}
    with pytest.raises(references.UnknownReference, match=str(name)):
        harness.load_parts(cell)


def test_default_is_the_reference_beside_the_package():
    for cfg in CONFIGS:
        assert "reference" not in config_of(cfg)
        assert references.path_for(config_of(cfg)) == \
            harness.HERE / "reference.py"


#: one cost changed in a copy of the default reference: DRAM energy +1%
PLANT = ('+ f(reads + writes) * e["dram"])',
         '+ f(reads + writes) * e["dram"] * f(1.01))')


@pytest.fixture
def planted(tmp_path, monkeypatch):
    text = (harness.HERE / "reference.py").read_text()
    assert text.count(PLANT[0]) == 1
    (tmp_path / "planted.py").write_text(text.replace(*PLANT))
    monkeypatch.setattr(references, "HERE", tmp_path)
    return "planted"


@pytest.mark.parametrize("cfg", CONFIGS)
def test_check_uses_the_named_reference(jax_engine, planted, cfg):
    sound = tiny(f"{cfg}.search")
    named = tiny(f"{cfg}.search", dict(config_of(cfg), reference=planted))
    assert references.path_for(named["config"]).name == "planted.py"
    w = window(sound)
    limits, n = sound["limits"], w["attempted"]
    assert check.verdict(check.check(w, sound), limits, n)
    assert not check.verdict(check.check(w, named), limits, n)
    # the control of each: float32 by the module its configuration names
    ctl_named = check.control_answers(w, named["config"])
    ctl_sound = check.control_answers(w, sound["config"])
    assert not check.verdict(check.check(ctl_named, named), limits, n)
    assert not check.verdict(check.check(ctl_sound, sound), limits, n)
    assert check.check(ctl_sound, sound).rel_gap < 1e-5
    assert check.check(ctl_named, sound).rel_gap > 1e-4
