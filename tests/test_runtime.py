"""Start-up pieces of the chip entry points that run on the CPU: the
compile-cache directory rule and chip_smoke's refusal to run without a
TPU."""
import os
import subprocess
import sys

import jax
import pytest

from repro import runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_cache_dir_from_environment(monkeypatch, tmp_path,
                                    restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, that directory and no other."""
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_fixed_inside_checkout(monkeypatch, restore_cache_config):
    """Unset: one fixed path inside the checkout, the same every call
    (a directory made from a temp name, pid or time would never hit)."""
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert runtime.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_not_enabled_at_import():
    """Importing the package changes no compile-cache setting."""
    code = ("import jax, repro, repro.runtime, repro.kernels, repro.serve;"
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop(runtime.CACHE_ENV, None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_cpu(where, tmp_path):
    """Without a TPU, chip_smoke exits non-zero, names the platform it
    found and prints no ok line — from the checkout, and from a
    directory that holds nothing else of the repo."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        alone = tmp_path / "chip_smoke.py"
        alone.write_text(open(script).read())
        script = str(alone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(script))
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
