"""``chip_smoke.py`` on the CPU: it refuses to run off the chip, and its
phase functions pass their own checks at the ``olmo-1b`` smoke size (the
mesh-commit phase on a 2x2 mesh of forced host devices).  Also where the
launchers' compile cache goes."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import make_debug_mesh, make_mesh

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses look it up
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_serve_phase(smoke):
    facts = smoke.serve_phase(make_debug_mesh(1), smoke=True, n_slots=2,
                              t_max=48, n_requests=5, prompt_len=16,
                              budgets=(6, 10, 14), commit_every=2)
    assert facts["ok"], facts
    assert facts["resumed_tick"] == 4 and facts["resumed_sessions"] == 2


def test_train_phase(smoke):
    facts = smoke.train_phase(get_smoke_config("olmo-1b"), make_debug_mesh(1),
                              global_batch=2, seq=16)
    assert facts["ok"], facts


def test_mesh_commit_phase(smoke, host_devices_8):
    facts = smoke.mesh_commit_phase(get_smoke_config("olmo-1b"),
                                    make_mesh((2, 2), ("data", "model")),
                                    global_batch=4, seq=16)
    assert facts["ok"], facts


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    import jax
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        want, configured = str(tmp_path), before   # JAX reads the variable
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
        configured = want
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == configured
    finally:
        # restored before anything compiles: the cache stays off in tests
        jax.config.update("jax_compilation_cache_dir", before)
