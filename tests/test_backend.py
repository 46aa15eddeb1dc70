"""utils/backend.py: the CPU only by name, and one compile-cache resolver.

Entry points need the TPU unless JAX_PLATFORMS names another platform
(require_backend); the persistent compile cache lives where
JAX_COMPILATION_CACHE_DIR says, else where --compile-cache-dir says, else
at a fixed directory inside the checkout (resolve_compile_cache).
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from karpenter_tpu.ops import pallas_binpack
from karpenter_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "env, flag, want",
    [
        ("/env/cache", "/flag/cache", "/env/cache"),  # the variable wins
        ("/env/cache", None, "/env/cache"),
        (None, "/flag/cache", "/flag/cache"),
        (None, None, os.path.join(REPO, ".jax_compile_cache")),
    ],
)
def test_resolve_compile_cache(monkeypatch, env, flag, want):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    path = backend.resolve_compile_cache(flag)
    assert path == want
    # a fixed path: no pid, no temporary directory, no timestamp
    assert str(os.getpid()) not in path
    assert not path.startswith(("/tmp", os.environ.get("TMPDIR") or "/tmp"))


def test_arm_with_the_variable_sets_no_other_directory(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    resolver writes no directory into JAX's config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert backend.arm_compile_cache("/some/flag") == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_entry_point_without_a_tpu_raises_instead_of_falling_back(tmp_path):
    """JAX_PLATFORMS unset and no TPU: the CLI exits nonzero with the
    reason, where JAX alone would quietly run on the CPU."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(
        PYTHONPATH=REPO, TPU_SKIP_MDS_QUERY="1",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    if probe.stdout.strip() == "tpu":
        pytest.skip("a TPU is attached here")
    proc = subprocess.run(
        [sys.executable, "-m", "karpenter_tpu", "--cloud-provider", "fake"],
        capture_output=True, text=True, env=env, timeout=120, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert "set JAX_PLATFORMS=cpu" in proc.stderr


def test_require_backend_honours_a_cpu_request():
    assert backend.require_backend() == "cpu"


@pytest.mark.parametrize("platform, interpret", [("cpu", True), ("tpu", False)])
def test_default_interpret(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert pallas_binpack.default_interpret() is interpret


def test_default_interpret_raises_on_an_unknown_platform(monkeypatch):
    """Neither TPU nor CPU: interpreting would pass for the compiled
    kernel, so it raises instead."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pallas_binpack.default_interpret()
