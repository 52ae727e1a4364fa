"""Start-up contracts of the device layer: no accelerator means an
error (never a quiet CPU), the compile cache has one resolver and one
name, and importing the package touches no backend."""
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import base, context
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- mx.tpu() / mx.gpu() -----------------------------------------------------

@pytest.mark.parametrize("factory", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_without_accelerator(factory):
    assert context._accelerators() == []      # the tier-1 host is a CPU
    with pytest.raises(MXNetError, match="no accelerator"):
        factory()
    with pytest.raises(MXNetError, match="no accelerator"):
        factory(3)


def test_lazy_accelerator_context_raises_at_resolution():
    """A Context built by name (checkpoint metadata, group2ctx) resolves
    late — and raises then rather than landing on a host device."""
    ctx = mx.Context("tpu", 0)
    with pytest.raises(MXNetError, match="no accelerator"):
        ctx.jax_device()
    with pytest.raises(MXNetError, match="no accelerator"):
        mx.nd.zeros((2,), ctx=ctx)
    assert mx.context.num_tpus() == 0 and mx.cpu().jax_device().platform \
        == "cpu"


def test_accelerator_context_resolves_and_bounds_checks(monkeypatch):
    devs = jax.devices("cpu")[:2]
    monkeypatch.setattr(context, "_accelerators", lambda: devs)
    assert mx.tpu(1).jax_device() is devs[1]
    with pytest.raises(MXNetError, match="out of range"):
        mx.tpu(2)


# -- the compile cache resolver ----------------------------------------------

@pytest.fixture
def cache_config(monkeypatch):
    """Record every directory handed to jax's cache option, touch none.
    `enable_compile_cache` hands a directory over only when jax's option
    differs from it, and whatever ran before on this worker (a cell's
    rehearsal calls the entry points' form) may have set the option: it is
    cleared here and put back afterwards."""
    seen = []
    real = jax.config.update
    before = jax.config.jax_compilation_cache_dir
    real("jax_compilation_cache_dir", None)

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
            return None
        if name.startswith("jax_persistent_cache_"):
            return None
        return real(name, value)
    monkeypatch.setattr(base._jax.config, "update", update)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
    yield seen
    real("jax_compilation_cache_dir", before)


def test_cache_dir_is_the_environments_when_set(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/cache")
    assert base.compile_cache_dir() == "/x/cache"
    assert base.enable_compile_cache() == "/x/cache"
    assert base.enable_compile_cache(default_to_checkout=True) == "/x/cache"
    assert set(cache_config) <= {"/x/cache"}, cache_config


def test_cache_dir_is_the_checkouts_when_unset(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert base.compile_cache_dir() == fixed
    # the library alone keeps no persistent cache ...
    assert base.enable_compile_cache() is None
    assert cache_config == []
    # ... the entry points ask for the checkout's, and get that path only
    assert base.enable_compile_cache(default_to_checkout=True) == fixed
    assert cache_config == [fixed]


def test_retired_cache_variable_is_ignored(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "/old/name")
    assert base.enable_compile_cache() is None and cache_config == []
    from mxnet_tpu.autotune import decisions
    monkeypatch.delenv("MXNET_AUTOTUNE_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/cache")
    assert decisions.decisions_dir() is None  # never hangs off the cache
    monkeypatch.setenv("MXNET_AUTOTUNE_DIR", "/own")
    assert decisions.decisions_dir() == "/own"


# -- import ------------------------------------------------------------------

def test_import_initialises_no_backend():
    """`import mxnet_tpu` must not create a JAX backend: a process that
    only imports (a launcher, a diagnostic's parent) holds no chip."""
    code = ("import mxnet_tpu; from jax._src import xla_bridge as xb; "
            "assert not xb.backends_are_initialized(), xb._backends; "
            "print('clean')")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    # no JAX_PLATFORMS pin: an initialised backend would show either way
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "clean" in r.stdout, r.stdout + r.stderr


def test_chip_smoke_exits_nonzero_in_the_device_phase_on_cpu():
    """No accelerator: non-zero exit, no result on stdout."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "not a TPU" in r.stderr
