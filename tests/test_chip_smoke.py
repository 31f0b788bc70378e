"""``chip_smoke.py``'s phases, rehearsed on the CPU at a tiny budget.

The script itself refuses to run anywhere but a TPU; its search and service
phases take the device they check against, so here they run on JAX's CPU
device: the jax engine must be used, its results bit-identical to the numpy
engine's, and every artifact must verify.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def jax_engine(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "jax")


def test_search_phase_jax_engine_matches_numpy(smoke, jax_engine):
    dev = jax.devices()[0]
    out = smoke.search_phase(dev, {"preset": "fast", "generations": 4})
    eng = out["engine"]
    assert eng["backend"] == "jax" and eng["batches"] > 0
    assert (eng["device_platform"], eng["device_kind"]) == \
        (dev.platform, dev.device_kind)
    assert out["generations"] == 4
    assert out["best_fitness"] >= 1.0


def test_search_phase_rejects_the_wrong_device(smoke, jax_engine):
    class Elsewhere:
        platform, device_kind = "tpu", "TPU v5 lite"

    with pytest.raises(smoke.SmokeError, match="labels lived on"):
        smoke.search_phase(Elsewhere(), {"preset": "fast", "generations": 1})


def test_service_phase_dedup_island_and_no_numpy_batches(smoke, jax_engine):
    out = smoke.service_phase(generations=3, timeout_s=300.0)
    states = [(wl, backend, state, outcome)
              for _id, wl, backend, state, outcome, _dd in out["jobs"]]
    assert states == [
        ("mobilenet_v3", "ga", "done", "searched"),
        ("resnet50", "ga", "done", "searched"),
        ("mobilenet_v3", "ga", "done", "cache_hit"),
        ("unet", "island", "done", "searched"),
    ]
    assert out["verified"] == 3
    assert out["batches_by_engine"]["eval.batches_by_engine{engine=jax}"] > 0
    assert "eval.batches_by_engine{engine=numpy}" not in \
        out["batches_by_engine"]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_without_a_tpu_fails_and_prints_no_result(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if where == "repo":
        assert "device: platform=cpu" in r.stdout
        assert "JAX found no TPU" in r.stderr
