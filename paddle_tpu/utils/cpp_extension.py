"""C++ extension loader — JIT-compile user C++ into callable ops.

Reference analog: python/paddle/utils/cpp_extension/cpp_extension.py
(load/CppExtension/CUDAExtension + custom_operator.cc .so loading).
TPU-native shape: user C++ runs on the HOST (there is no user ISA on
the TPU core — the reference's CUDA path maps to Pallas kernels, see
paddle_tpu/kernels/). The compiled function is bridged into jax with
jax.pure_callback, so it works both eagerly and inside jit (XLA
round-trips the buffer to the host, like the reference's CPU custom
kernels do from GPU graphs).

C ABI contract (one function per op):
    extern "C" void fn(const float** ins, const int64_t* sizes,
                       int n_ins, float* out, int64_t out_size);
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.op_registry import op as _register_op

__all__ = ["load", "CppExtensionModule", "get_build_directory"]

_BUILD_DIR = os.environ.get(
    "PADDLE_EXTENSION_DIR",
    os.path.join(tempfile.gettempdir(), "paddle_tpu_extensions"))


def get_build_directory() -> str:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    return _BUILD_DIR


def _compile(name: str, sources: Sequence[str],
             extra_cxx_flags: Sequence[str] = (),
             verbose: bool = False) -> str:
    """g++ -shared -fPIC the sources; content-hash keyed cache."""
    build_dir = get_build_directory()
    h = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(extra_cxx_flags).encode())
    so_path = os.path.join(build_dir, f"{name}_{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    # build to a temp path + atomic rename: a killed/concurrent build
    # must never leave a truncated .so at the cached path
    tmp_path = f"{so_path}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           *extra_cxx_flags, *sources, "-o", tmp_path]
    if verbose:
        print("cpp_extension:", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp_path, so_path)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"cpp_extension build failed:\n{e.stderr}") from e
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
    return so_path


class CppExtensionModule:
    """Wraps a compiled .so; def_op() turns exported symbols into
    registered framework ops."""

    def __init__(self, name: str, so_path: str):
        self.name = name
        self.so_path = so_path
        self._lib = ctypes.CDLL(so_path)

    def def_op(self, fn_name: str,
               out_shape: Optional[Callable] = None,
               out_dtype=np.float32,
               op_name: Optional[str] = None) -> Callable:
        """Expose `fn_name` (C ABI above) as a framework op.

        out_shape: callable(*input_shapes) -> output shape; defaults to
        the first input's shape (elementwise ops).
        """
        cfn = getattr(self._lib, fn_name)
        cfn.restype = None
        cfn.argtypes = [ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
                        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
        shape_fn = out_shape or (lambda *shapes: shapes[0])

        def host_call(*arrays: np.ndarray) -> np.ndarray:
            arrs = [np.ascontiguousarray(a, dtype=np.float32)
                    for a in arrays]
            n = len(arrs)
            ptrs = (ctypes.POINTER(ctypes.c_float) * n)(*[
                a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                for a in arrs])
            sizes = (ctypes.c_int64 * n)(*[a.size for a in arrs])
            oshape = shape_fn(*[a.shape for a in arrs])
            out = np.empty(oshape, dtype=np.float32)
            cfn(ptrs, sizes, n,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                out.size)
            return out.astype(out_dtype, copy=False)

        def impl(*xs):
            if not any(isinstance(x, jax.core.Tracer) for x in xs):
                # eager: call the C function directly on host buffers
                return jnp.asarray(host_call(*[np.asarray(x)
                                               for x in xs]))
            oshape = shape_fn(*[tuple(x.shape) for x in xs])
            result_sds = jax.ShapeDtypeStruct(tuple(oshape),
                                              jnp.dtype(out_dtype))
            return jax.pure_callback(host_call, result_sds, *xs,
                                     vmap_method="sequential")

        impl.__name__ = fn_name
        public = _register_op(op_name or f"{self.name}::{fn_name}",
                              differentiable=False)(impl)
        setattr(self, fn_name, public)
        return public


def load(name: str, sources: Sequence[str],
         extra_cxx_flags: Sequence[str] = (),
         verbose: bool = False) -> CppExtensionModule:
    """Compile + load a C++ extension (reference cpp_extension.load)."""
    so_path = _compile(name, sources, extra_cxx_flags, verbose)
    return CppExtensionModule(name, so_path)
