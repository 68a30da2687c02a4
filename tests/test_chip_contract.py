"""Where the chip is asked for, nothing else answers quietly.

CPU-side checks of the rules ``chip_smoke.py`` rests on: a missing TPU
raises instead of landing on the CPU, a device without a published peak
is an error, the compile cache is placed by one rule, the native
library is keyed on its sources, a failed bench or kernel is loud, and
``chip_smoke.py`` refuses to start without the chip.
"""
import os
import shutil
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import device as core_device
from paddle_tpu.jit import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ set_device

@pytest.fixture
def restore_device():
    prev, prev_default = core_device._CURRENT, jax.config.jax_default_device
    yield
    core_device._CURRENT = prev
    jax.config.update("jax_default_device", prev_default)


@pytest.mark.parametrize("name", ["tpu", "tpu:0", "gpu", "gpu:0", "xpu",
                                  "npu", "cuda"])
def test_set_device_tpu_raises_without_a_tpu(restore_device, name):
    with pytest.raises(RuntimeError, match="no tpu device is visible"):
        paddle.set_device(name)
    assert core_device._CURRENT is None or \
        not core_device._CURRENT.startswith("tpu")


def test_set_device_cpu_works(restore_device):
    place = paddle.set_device("cpu")
    assert place.is_cpu_place() and paddle.get_device() == "cpu:0"
    with pytest.raises(ValueError, match="out of range"):
        paddle.set_device("cpu:99")


# ------------------------------------------------------------ peak table

def _device(kind, platform="tpu"):
    return types.SimpleNamespace(device_kind=kind, platform=platform)


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v4", 275e12),
                                       ("TPU v6 lite", 918e12)])
def test_peak_flops_known_kinds(kind, peak):
    import bench
    assert bench.peak_flops(_device(kind)) == peak


@pytest.mark.parametrize("kind,platform", [("cpu", "cpu"),
                                           ("TPU v9 mega", "tpu"),
                                           ("", "tpu")])
def test_peak_flops_unknown_kind_raises(kind, platform):
    import bench
    with pytest.raises(ValueError, match="no published peak"):
        bench.peak_flops(_device(kind, platform))


def test_bench_all_exits_nonzero_when_a_bench_fails(monkeypatch, capsys):
    import bench

    def boom(dev, on_tpu):
        raise RuntimeError("boom")

    def fine(dev, on_tpu):
        return {"metric": "fine", "value": 1.0}

    monkeypatch.setattr(bench, "BENCHES", {"gpt2": fine, "other": boom})
    monkeypatch.setattr(bench, "bench_gpt2", fine)
    monkeypatch.setattr(bench, "_setup",
                        lambda configure_cache=True: (None, False))
    monkeypatch.setattr(sys, "argv", ["bench.py", "all"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert "other FAILED: boom" in out.err and '"fine"' in out.out


# ------------------------------------------------- compile cache, one rule

@pytest.fixture
def fresh_cache_state(monkeypatch):
    """enable_compile_cache() as a new process would see it, with the
    jax config writes recorded instead of applied."""
    monkeypatch.setattr(compile_cache, "_CACHE_DIR", None)
    monkeypatch.setattr(compile_cache, "_DEFAULT_STORE", None)
    writes = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: writes.__setitem__(key, value))
    return writes


def test_compile_cache_placed_by_the_environment(fresh_cache_state,
                                                 monkeypatch, tmp_path):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    store = compile_cache.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in fresh_cache_state
    assert compile_cache.cache_dir() == placed
    assert store.root == os.path.join(placed, "executables")
    # a caller's own path loses to the environment, loudly
    with pytest.warns(UserWarning, match="process-global"):
        compile_cache.enable_compile_cache(str(tmp_path / "mine"))
    assert "jax_compilation_cache_dir" not in fresh_cache_state
    assert compile_cache.cache_dir() == placed


def test_compile_cache_defaults_to_the_checkout(fresh_cache_state,
                                                monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_root() == fixed
    store = compile_cache.enable_compile_cache()
    assert fresh_cache_state["jax_compilation_cache_dir"] == fixed
    assert compile_cache.cache_dir() == fixed
    assert store.root == os.path.join(fixed, "executables")


def test_compile_cache_argument_when_unplaced(fresh_cache_state,
                                              monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    mine = str(tmp_path / "mine")
    compile_cache.enable_compile_cache(mine)
    assert fresh_cache_state["jax_compilation_cache_dir"] == mine
    assert compile_cache.default_store().root == \
        os.path.join(mine, "executables")


def test_no_store_until_enabled(fresh_cache_state, monkeypatch, tmp_path):
    """No environment variable turns the executable store on by itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.default_store() is None


def test_store_loads_onto_the_devices_it_was_compiled_for(tmp_path):
    """A one-device program read back from the store runs on a backend
    with several devices (8 virtual ones here, 4 chips on a host)."""
    import jax.numpy as jnp
    assert jax.device_count() > 1
    store = compile_cache.ExecutableStore(str(tmp_path))
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    with jax.default_device(jax.devices()[3]):
        store.get_or_compile(jax.jit(lambda x: x + 1).lower(aval))
        exe = store.get_or_compile(jax.jit(lambda x: x + 1).lower(aval))
        assert store.stats["hits"] == 1
        out = exe(jnp.zeros((8,), jnp.float32))
    assert out.devices() == {jax.devices()[3]}
    np.testing.assert_array_equal(np.asarray(out), np.ones(8))


# ---------------------------------------------------------- native library

def test_native_library_is_keyed_on_source_content(monkeypatch, tmp_path):
    from paddle_tpu import native
    for src in native._SOURCES:
        shutil.copy(os.path.join(native._DIR, src), tmp_path)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    before = native._so_path()
    assert before == native._so_path()
    os.utime(tmp_path / native._SOURCES[0], (0, 0))   # mtime is no key
    assert native._so_path() == before
    with open(tmp_path / native._SOURCES[0], "a") as f:
        f.write("\n// edited\n")
    assert native._so_path() != before


# ------------------------------------------------- kernel failures are loud

@pytest.mark.parametrize("error,falls_back", [
    (NotImplementedError("declared unsupported shape"), True),
    (RuntimeError("kernel regression"), False),
])
def test_flash_failure_is_not_swallowed(monkeypatch, error, falls_back):
    import importlib
    import paddle_tpu.nn.functional as F
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    def broken(*a, **k):
        raise error

    monkeypatch.setattr(fa, "flash_attention", broken)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = paddle.to_tensor(np.zeros((1, 512, 2, 64), np.float32))
    if falls_back:
        out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert tuple(out.shape) == (1, 512, 2, 64)
    else:
        with pytest.raises(RuntimeError, match="kernel regression"):
            F.scaled_dot_product_attention(q, q, q, is_causal=True)


# ------------------------------------------------------------ chip_smoke

@pytest.mark.parametrize("args", [[], ["--four-chips"]],
                         ids=["one-chip", "four-chips"])
def test_chip_smoke_refuses_without_the_chip(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "nothing was run" in proc.stderr
    assert proc.stdout.strip() == ""       # no phase line, no result line
