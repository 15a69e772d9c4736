"""Entry-point plumbing shared by chip_smoke.py, launch/serve.py,
launch/train.py and benchmarks/: the compile cache, the device line and
the jitted parameter init."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache as CC
from repro.launch.serve import device_line, init_params
from repro.models import get_model_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_fixed_path_in_checkout(
    monkeypatch, cache_dir_config
):
    monkeypatch.delenv(CC.ENV, raising=False)
    path = CC.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert CC.enable_compile_cache() == path   # the same path every call


def test_compile_cache_env_var_is_left_to_jax(
    monkeypatch, cache_dir_config, tmp_path
):
    monkeypatch.setenv(CC.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_device_line_names_platform_kind_and_count():
    d = jax.devices()
    assert device_line() == (
        f"device: platform={d[0].platform} kind={d[0].device_kind} "
        f"count={len(d)}"
    )


def test_jitted_init_matches_eager_init():
    cfg = get_smoke_config("stablelm-3b")
    eager = get_model_fns(cfg).init(jax.random.PRNGKey(3), cfg)
    jitted = init_params(cfg, seed=3)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_and_smoke_paths_leave_xla_flags_alone():
    """``launch/dryrun.py`` sets XLA_FLAGS (512 fake devices) at import:
    nothing on the serve or smoke path may import it."""
    code = (
        "import os, sys; sys.path.insert(0, %r); "
        "import chip_smoke, repro.launch.serve; "
        "print('dryrun' in ' '.join(sys.modules), "
        "'XLA_FLAGS' in os.environ)" % REPO
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "False"]
