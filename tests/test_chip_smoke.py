"""`chip_smoke.py` rehearsed on the CPU: its phase functions at the default
`Scale()` in interpret mode, its refusal to run without a TPU, and where
it points the compile cache."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.mvcc.workload import Scale

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_rehearse_on_cpu(smoke):
    sc = Scale()
    k = smoke._phase(smoke.kernel_phase, sc)
    assert k["pages"] == smoke.store_pages(sc)
    assert set(k["kernels"]) == {"scalar", "flat_grouped",
                                 "chunked_grouped", "delta_fold"}
    assert k["compiles"] > 0 and k["wall_s"] > 0
    for phase in (smoke.single_node_phase, smoke.multi_node_phase):
        out = smoke._phase(phase, sc, rounds=60)
        assert out["olap_aborts"] == 0 and out["olap_wait_rounds"] == 0
        assert out["pallas_calls"] > 0 and out["view_hits"] > 0
        assert min(out["plan_steps_checked"].values()) >= 2
    assert out["token_violations"] == 0
    json.dumps(out, default=str)


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 1
    cap = capsys.readouterr()
    assert cap.out == "" and "needs a TPU" in cap.err


def test_script_alone_fails(tmp_path):
    """Copied out of the repo, the script fails and prints no result."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_location(smoke, monkeypatch, env_dir):
    """The environment's cache directory wins and nothing is set in code;
    otherwise one fixed directory inside the checkout."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert smoke._configure_compile_cache() == str(REPO / ".jax_cache")
        assert calls["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert smoke._configure_compile_cache() == env_dir
        assert calls == {}
