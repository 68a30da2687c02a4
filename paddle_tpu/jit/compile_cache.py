"""Executable persistence: the warm-restart layer (ROADMAP item 4).

Every relaunch in this stack is BY DESIGN — preemption-safe training
exits and resumes, the serving engine AOT-warms one prefill executable
per bucket plus the decode/admit/free trio on every process start — and
each relaunch used to re-pay tens of seconds of XLA work. This module
makes a relaunched process warm-start in seconds, two layers deep:

1. **The process-global jax persistent compilation cache.**
   ``enable_compile_cache()`` turns jax's own HLO->binary disk cache
   on. Where it lives follows one rule (:func:`cache_root`): if
   ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and this
   module sets no dir at all; otherwise the caller's ``dir``, else the
   fixed ``<checkout>/.jax_cache`` (the path is part of jax's cache
   key, so it never moves). The jax cache dir is process-global state:
   it is placed ONCE and never silently re-pointed — a second caller
   naming a different dir gets a warning and the original dir
   (predictor B must not hijack predictor A's cache). This module is
   the only place allowed to touch ``jax_compilation_cache_dir`` (lint
   rule ``compile-cache-dir``).

2. **The executable store above it.** jax's cache keys on internals
   and still re-runs part of the compile pipeline on a hit; the
   :class:`ExecutableStore` instead persists whole compiled
   executables (``jax.experimental.serialize_executable``) keyed by
   (StableHLO fingerprint, mesh/sharding signature, donation
   signature, jax/jaxlib version, backend platform + device kind +
   device count). A hit deserializes straight to a callable
   ``jax.stages.Compiled`` — zero XLA compiles — in ~tens of
   milliseconds. Every AOT path threads through
   :func:`compile_or_load`: ``GenerationSession.aot_compile``, the
   ``ServingEngine.warmup()`` program set, the Predictor's per-bucket
   build, and the ``TrainStep``/``DistributedTrainStep`` opt-in warm
   path behind ``Model.fit(resume=True)``.

3. **The traceless manifest.** Even a store hit still pays the jax
   TRACE to produce the StableHLO the key hashes — and on relaunch,
   tracing every program costs nearly as much as compiling small ones.
   So the store keeps a second, derived level: ``.ref`` manifest
   entries mapping a *structural program signature* — framework + model
   **source hashes**, parameter/operand structure, generation/serving
   config reprs, donation, mesh, versions, backend — to the HLO key of
   the executable it produced. A warm relaunch resolves the signature,
   reads the ref, and deserializes the executable with ZERO traces and
   zero compiles; any doubt (no deterministic signature, missing ref,
   ref pointing at a dropped entry) falls back to the traced path,
   which is always correct and rewrites the ref.
   ``PADDLE_COMPILE_CACHE_VERIFY=1`` is the paranoid mode: the trace
   runs anyway and a ref whose stored key disagrees with the real
   fingerprint is recorded as ``misses{cause=stale_ref}`` and replaced
   — CI can prove the manifest honest.

Durability follows the CheckpointManager commit-marker idiom: entries
are written to a temp file and atomically renamed (a torn write is
never visible under the final name), carry a sha256 checksum, and a
corrupt/truncated/version-skewed entry is NEVER fatal — the load
falls back to a fresh compile, records
``jit.compile_cache.misses{cause=corrupt}``, removes the bad entry,
and rewrites a good one.

Reference analog: the reference ships this layer as serialized
inference programs in ``paddle/fluid/inference`` (PAPER.md §1) —
``save_optimized_model`` + the NaiveExecutor loading pre-analyzed
program descs; here the serialized artifact is the XLA executable
itself.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
import warnings
from typing import Any, Dict, List, Optional

import jax

from ..core import flight_recorder, monitor

__all__ = [
    "ExecutableStore",
    "aval_signature",
    "build_or_load",
    "cache_key",
    "callable_signature",
    "compile_or_load",
    "compile_source",
    "default_store",
    "enable_compile_cache",
    "network_signature",
    "persistent_cache_hits",
    "scalar_signature",
    "set_default_store",
    "source_hash",
]

#: executable-entry file layout: MAGIC + 64 hex sha256(payload) + payload
_MAGIC = b"PDTPU-EXE2\n"
#: manifest-entry layout: REF_MAGIC + 64 hex chars (the executable key)
_REF_MAGIC = b"PDTPU-REF1\n"
ENTRY_SUFFIX = ".pdexe"
REF_SUFFIX = ".ref"

_lock = threading.RLock()
_CACHE_DIR: Optional[str] = None
_DEFAULT_STORE: Optional["ExecutableStore"] = None


# --------------------------------------------------- process-global cache

_persistent_hits = 0
_listening = False


def _on_jax_event(event: str, **_):
    global _persistent_hits
    if event == "/jax/compilation_cache/cache_hits":
        _persistent_hits += 1


def persistent_cache_hits() -> int:
    """How many XLA compiles jax's persistent compilation cache has
    answered in this process since the first call (which registers the
    listener): a count that moved across a ``compile()`` means the
    binary came from disk."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_jax_event)
        _listening = True
    return _persistent_hits


def compile_source(hits_before: int) -> str:
    """``persistent_cache`` or ``compile``: where the XLA compile that
    ran since ``hits_before = persistent_cache_hits()`` got its binary
    (the ``source`` field of a ``jit.program`` span)."""
    return "persistent_cache" if persistent_cache_hits() > hits_before \
        else "compile"


def _compile(lowered, info: dict):
    hits = persistent_cache_hits()
    exe = lowered.compile()
    info["source"] = compile_source(hits)
    return exe


def _lower(lower_fn, info: dict):
    t0 = flight_recorder.now_ns()
    lowered = lower_fn()
    info["lower_s"] = round((flight_recorder.now_ns() - t0) * 1e-9, 6)
    return lowered


def _placed_by_env() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()


def cache_root(path: Optional[str] = None) -> str:
    """Where the compile cache lives: ``JAX_COMPILATION_CACHE_DIR`` if
    the environment places it, else ``path``, else the fixed
    ``<checkout>/.jax_cache``."""
    import paddle_tpu
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    return _placed_by_env() or path \
        or os.path.join(checkout, ".jax_cache")


def enable_compile_cache(path: Optional[str] = None,
                         min_compile_time_secs: float = 0.0
                         ) -> "ExecutableStore":
    """Turn jax's persistent compilation cache on at
    :func:`cache_root` and anchor the process-default
    :class:`ExecutableStore` at ``<root>/executables``. Returns the
    store. With ``JAX_COMPILATION_CACHE_DIR`` set jax has already read
    its dir from the environment and none is set here.

    The jax cache dir is process-global; it is placed once and a later
    call naming a DIFFERENT path warns and keeps the original (the
    same conflict semantics the inference predictor always had —
    ``Config.enable_compile_cache`` delegates here)."""
    global _CACHE_DIR, _DEFAULT_STORE
    with _lock:
        if _CACHE_DIR is None:
            root = cache_root(path)
            os.makedirs(root, exist_ok=True)
            if not _placed_by_env():
                jax.config.update("jax_compilation_cache_dir", root)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              float(min_compile_time_secs))
            _CACHE_DIR = root
            _DEFAULT_STORE = ExecutableStore(
                os.path.join(root, "executables"))
        if path and os.path.abspath(path) != os.path.abspath(_CACHE_DIR):
            warnings.warn(
                f"compile cache already at {_CACHE_DIR!r}; the jax "
                f"cache dir is process-global, ignoring {path!r}")
        return _DEFAULT_STORE


def cache_dir() -> Optional[str]:
    """The process-global persistent-cache dir (None until enabled)."""
    return _CACHE_DIR


def default_store() -> Optional["ExecutableStore"]:
    """The process-default executable store: the one
    :func:`enable_compile_cache` anchored, else None (AOT paths then
    compile directly, persisting nothing)."""
    with _lock:
        return _DEFAULT_STORE


def set_default_store(store: Optional["ExecutableStore"]
                      ) -> Optional["ExecutableStore"]:
    """Swap the process-default store (embedding apps, tests). Returns
    the previous default. Does NOT touch the jax persistent-cache dir —
    that stays set-once."""
    global _DEFAULT_STORE
    with _lock:
        prev, _DEFAULT_STORE = _DEFAULT_STORE, store
        return prev


# --------------------------------------------------------------- cache key

def backend_signature() -> Dict[str, Any]:
    """The environment half of the cache key: an executable is only
    loadable into the runtime flavor that produced it."""
    import jaxlib
    dev = jax.devices()[0]
    return dict(
        jax_version=jax.__version__,
        jaxlib_version=jaxlib.__version__,
        backend=dev.platform,
        device_kind=getattr(dev, "device_kind", ""),
        n_devices=jax.device_count(),
    )


def cache_key(hlo_fingerprint: str, *, extra: Optional[dict] = None,
              **overrides) -> str:
    """Derive the store key for one program. ``hlo_fingerprint`` is the
    sha256 of the lowered StableHLO text (shapes, dtypes, shardings and
    the sampling/config constants are all in there); ``extra`` carries
    the caller-declared components the HLO text cannot be trusted to
    encode on every backend — donation signature, mesh axes, program
    kind. ``overrides`` replace :func:`backend_signature` fields
    (tests prove a changed jaxlib/backend string MISSES).

    Changing ANY component must change the key: a stale hit that
    silently serves the wrong program is the failure mode this
    derivation exists to make impossible."""
    parts = backend_signature()
    parts.update(overrides)
    parts["hlo"] = str(hlo_fingerprint)
    if extra:
        parts["extra"] = tuple(sorted(
            (str(k), str(v)) for k, v in extra.items()))
    canon = repr(tuple(sorted((k, str(v)) for k, v in parts.items())))
    return hashlib.sha256(canon.encode("utf-8")).hexdigest()


def fingerprint_lowered(lowered) -> str:
    """sha256 of the lowered module's StableHLO text — deterministic
    across fresh traces of the same program."""
    return hashlib.sha256(lowered.as_text().encode("utf-8")).hexdigest()


# ----------------------------------------------- structural signatures
#
# The traceless manifest needs a deterministic description of "the
# program this call site would trace" WITHOUT tracing it. Program
# identity = code that builds the trace + operand structure + static
# config; the helpers below hash exactly that, and return None whenever
# no deterministic description exists — callers then use the traced
# path, which is always correct.

#: framework source whose edits can change traced program STRUCTURE; a
#: manifest written by different source must never resolve. The bias is
#: deliberately broad — every .py under these trees joins the salt, so
#: an edited layer/op/kernel/optimizer costs one extra cold compile
#: after the edit instead of ever risking a stale traceless hit.
_SALT_DIRS = (
    "nn", "ops", "kernels", "optimizer", "generation", "amp",
    "distributed/fleet",
)
_SALT_FILES = (
    "jit/api.py",
    "serving/engine.py",
    "serving/programs.py",
    "inference/precision.py",
    "core/tensor.py",
    # pieces a model file assembles from outside its own module
    "models/decoder.py",
    "distributed/parallel/moe.py",
)
_framework_salt_cache: Optional[str] = None


def _framework_salt() -> str:
    global _framework_salt_cache
    if _framework_salt_cache is None:
        import paddle_tpu
        root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
        h = hashlib.sha256(
            str(getattr(paddle_tpu, "__version__", "")).encode())

        def feed(path, rel):
            try:
                with open(path, "rb") as f:
                    h.update(rel.encode())
                    h.update(hashlib.sha256(f.read()).digest())
            except OSError:
                h.update(b"missing:" + rel.encode())

        for rel in _SALT_FILES:
            feed(os.path.join(root, rel), rel)
        for d in _SALT_DIRS:
            base = os.path.join(root, d)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames.sort()
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        p = os.path.join(dirpath, name)
                        feed(p, os.path.relpath(p, root))
        _framework_salt_cache = h.hexdigest()
    return _framework_salt_cache


def source_hash(obj) -> Optional[str]:
    """sha256 of the object's source (class, function, lambda-in-file);
    None when no source is reachable (REPL lambdas, builtins) — the
    caller must then fall back to the traced path."""
    import inspect
    try:
        src = inspect.getsource(obj)
    except (OSError, TypeError):
        return None
    return hashlib.sha256(src.encode("utf-8")).hexdigest()


def aval_signature(tree) -> tuple:
    """(treedef, ((shape, dtype), ...)) of a pytree of arrays /
    ShapeDtypeStructs — the operand-structure half of a program
    signature, readable without any device work."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = []
    for x in leaves:
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sig.append((tuple(x.shape), str(x.dtype)))
        else:
            sig.append((repr(x),))
    return (str(treedef), tuple(sig))


def network_signature(network) -> Optional[dict]:
    """Structural identity of a live Layer without tracing it: class
    qualname + the SOURCE hash of its whole defining module (the trunk
    classes and helpers a model file executes live next to the class —
    hashing only the class block would miss them) + its config
    dataclass (or an address-free repr) + parameter/buffer structure +
    the framework salt (every nn/ops/kernels/optimizer source file).
    None when any piece is non-deterministic (e.g. a repr carrying
    object addresses) — then there is no sound traceless key and the
    traced path must be used."""
    import sys
    cls = type(network)
    mod_file = getattr(sys.modules.get(cls.__module__), "__file__",
                       None)
    cls_src = None
    if mod_file is not None:
        try:
            with open(mod_file, "rb") as f:
                cls_src = hashlib.sha256(f.read()).hexdigest()
        except OSError:
            cls_src = None
    if cls_src is None:
        cls_src = source_hash(cls)   # REPL/zip: class block only
    if cls_src is None:
        return None
    sig = dict(cls=f"{cls.__module__}.{cls.__qualname__}",
               cls_src=cls_src, salt=_framework_salt())
    cfg = getattr(network, "cfg", None)
    desc = repr(cfg) if cfg is not None else repr(network)
    if "0x" in desc:   # id()-bearing repr: not stable across processes
        return None
    sig["net"] = desc
    try:
        state = network.state_dict()
        sig["state"] = tuple(
            (name, tuple(t.shape), str(t.dtype))
            for name, t in state.items())
    except Exception:
        return None
    return sig


def scalar_signature(obj) -> tuple:
    """The plain-scalar attributes of an object, sorted — the baked
    trace-time constants an optimizer/config instance contributes to a
    program (betas, eps, weight decay, ...)."""
    out = []
    try:
        attrs = vars(obj)
    except TypeError:
        return ()
    for k in sorted(attrs):
        v = attrs[k]
        if isinstance(v, (int, float, bool, str, bytes)) or v is None:
            out.append((k, repr(v)))
    return tuple(out)


def callable_signature(fn, _depth: int = 0) -> Optional[tuple]:
    """Source hash of a callable PLUS the identifiable values it closes
    over (scalars are baked into the trace as constants; closed-over
    callables/Layers recurse). None when anything in the closure cannot
    be identified deterministically — then no traceless key exists."""
    src = source_hash(fn)
    if src is None or _depth > 4:
        return None
    parts = []
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            return None
        if isinstance(v, (int, float, bool, str, bytes)) or v is None:
            parts.append(repr(v))
        elif hasattr(v, "state_dict"):
            ns = network_signature(v)
            if ns is None:
                return None
            parts.append(tuple(sorted(
                (k, str(x)) for k, x in ns.items())))
        elif callable(v):
            inner = callable_signature(v, _depth + 1)
            if inner is None:
                return None
            parts.append(inner)
        else:
            return None   # unidentifiable baked operand
    return (src, tuple(parts))


def _signature_key(signature: dict, extra: Optional[dict]) -> str:
    canon = repr(tuple(sorted(
        (str(k), str(v)) for k, v in signature.items())))
    return cache_key("ref:" + hashlib.sha256(
        canon.encode("utf-8")).hexdigest(), extra=extra)


def _verify_mode() -> bool:
    return os.environ.get("PADDLE_COMPILE_CACHE_VERIFY",
                          "").strip().lower() in ("1", "true", "on")


# ------------------------------------------------------------------- store

class ExecutableStore:
    """Directory of serialized compiled executables, one file per key.

    ::

        store = ExecutableStore("/ckpt/compile_cache/executables")
        exe = store.get_or_compile(jitted.lower(*avals),
                                   extra=dict(kind="decode",
                                              donation=(2,)))

    Writes are atomic (temp file + ``os.replace`` — the commit-marker
    idiom collapsed to a single-file rename), loads are
    corruption-tolerant (checksum + magic; any failure removes the bad
    entry and returns None so the caller recompiles), and every
    hit/miss/byte flows into the ``jit.compile_cache.*`` metrics family
    as well as the instance-local ``stats`` dict (readable without the
    monitor enabled — bench reads it)."""

    def __init__(self, root: str):
        self.root = str(root)
        self.stats = dict(hits=0, misses=0, saves=0,
                          bytes_loaded=0, bytes_saved=0)

    # ------------------------------------------------------------ layout
    def path_for(self, key: str) -> str:
        return os.path.join(self.root, key + ENTRY_SUFFIX)

    def entries(self) -> List[str]:
        """Sorted entry paths (deterministic handle for fault
        injection)."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(os.path.join(self.root, n) for n in names
                      if n.endswith(ENTRY_SUFFIX))

    def __len__(self) -> int:
        return len(self.entries())

    def key_for(self, lowered, *, extra: Optional[dict] = None,
                **overrides) -> str:
        return cache_key(fingerprint_lowered(lowered), extra=extra,
                         **overrides)

    # -------------------------------------------------------------- load
    def load(self, key: str, label: str = "") -> Optional[Any]:
        """A ``jax.stages.Compiled`` for ``key``, or None (absent or
        corrupt — corrupt entries are deleted and recorded as
        ``misses{cause=corrupt}`` so the next save rewrites a good
        one)."""
        path = self.path_for(key)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self._miss("absent")
            return None
        try:
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            digest = blob[len(_MAGIC):len(_MAGIC) + 64]
            payload = blob[len(_MAGIC) + 64:]
            if hashlib.sha256(payload).hexdigest().encode() != digest:
                raise ValueError("checksum mismatch (torn/corrupt entry)")
            from jax.experimental import serialize_executable as _se
            serialized, in_tree, out_tree, device_ids = \
                pickle.loads(payload)
            # load onto the devices the program was compiled for: left
            # to its default, jax loads it across every device of the
            # backend, and a one-device program then refuses its
            # operands on a host with several
            by_id = {d.id: d for d in jax.devices()}
            exe = _se.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            # a bad entry must never crash a relaunch: recompile instead
            # (and drop the entry so the fresh compile rewrites it)
            self._miss("corrupt")
            monitor.record_swallowed(f"jit.compile_cache.load[{label}]", e)
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        load_ms = (time.perf_counter() - t0) * 1e3
        self.stats["hits"] += 1
        self.stats["bytes_loaded"] += len(blob)
        monitor.record_compile_cache_hit(len(blob), load_ms)
        return exe

    def _miss(self, cause: str):
        self.stats["misses"] += 1
        monitor.record_compile_cache_miss(cause)

    # -------------------------------------------------------------- save
    def save(self, key: str, compiled, label: str = "") -> bool:
        """Serialize + atomically commit one executable; False when the
        backend/executable does not support serialization (recorded,
        never raised — persistence is an optimization, not a
        contract)."""
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as _se
            serialized, in_tree, out_tree = _se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            payload = pickle.dumps(
                (serialized, in_tree, out_tree, device_ids),
                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            monitor.record_swallowed(f"jit.compile_cache.save[{label}]", e)
            return False
        blob = _MAGIC + hashlib.sha256(payload).hexdigest().encode() \
            + payload
        path = self.path_for(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            # makedirs inside the guard: an unwritable/uncreatable
            # store root degrades to no-persistence, never to a
            # crashed training/serving step
            os.makedirs(self.root, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic commit: readers see a whole
            #                        entry under the final name, or none
        except OSError as e:
            monitor.record_swallowed(f"jit.compile_cache.save[{label}]", e)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        save_ms = (time.perf_counter() - t0) * 1e3
        self.stats["saves"] += 1
        self.stats["bytes_saved"] += len(blob)
        monitor.record_compile_cache_save(len(blob), save_ms)
        return True

    # ----------------------------------------------------- the manifest
    def _ref_path(self, ref_key: str) -> str:
        return os.path.join(self.root, ref_key + REF_SUFFIX)

    def _read_ref(self, ref_key: str) -> Optional[str]:
        try:
            with open(self._ref_path(ref_key), "rb") as f:
                blob = f.read()
        except OSError:
            return None
        if not blob.startswith(_REF_MAGIC):
            return None
        key = blob[len(_REF_MAGIC):].decode("ascii", "replace").strip()
        if len(key) != 64 or any(c not in "0123456789abcdef"
                                 for c in key):
            return None   # corrupt ref: treated as absent
        return key

    def _write_ref(self, ref_key: str, exe_key: str):
        path = self._ref_path(ref_key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self.root, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(_REF_MAGIC + exe_key.encode("ascii"))
            os.replace(tmp, path)
        except OSError as e:
            monitor.record_swallowed("jit.compile_cache.ref", e)
            try:
                os.remove(tmp)
            except OSError:
                pass

    # ---------------------------------------------------------- combined
    def _moved_bytes(self) -> int:
        return self.stats["bytes_loaded"] + self.stats["bytes_saved"]

    def _load_or_compile(self, key: str, lowered, label: str,
                         info: dict, load: bool = True):
        """The entry under ``key``, else a fresh compile that is then
        persisted; ``info`` learns which (``source``) and the bytes
        read or written."""
        b0 = self._moved_bytes()
        exe = self.load(key, label=label) if load else None
        if exe is not None:
            info["source"] = "store"
        else:
            exe = _compile(lowered, info)
            self.save(key, exe, label=label)
        info["bytes"] = self._moved_bytes() - b0
        return exe

    def get_or_compile(self, lowered, *, extra: Optional[dict] = None,
                       label: str = "", info: Optional[dict] = None):
        """The traced AOT entry point: key the lowered program, load
        the stored executable on a hit (zero XLA compiles), else
        compile and persist. Always returns a callable ``Compiled``.
        ``info`` (the ``jit.program`` span's fields) is filled in."""
        return self._load_or_compile(
            self.key_for(lowered, extra=extra), lowered, label,
            {} if info is None else info)

    def get_or_build(self, signature: Optional[dict], lower_fn, *,
                     extra: Optional[dict] = None, label: str = "",
                     info: Optional[dict] = None):
        """The TRACELESS AOT entry point. ``signature`` structurally
        identifies the program (see :func:`network_signature` /
        :func:`aval_signature`); on a manifest hit the executable
        deserializes with zero traces AND zero compiles — ``lower_fn``
        is never called. Every doubt (``signature`` None, no ref, ref
        pointing at a dropped entry) falls back to
        ``lower_fn() -> get_or_compile`` — always correct — and
        rewrites the ref for the next relaunch. Under
        ``PADDLE_COMPILE_CACHE_VERIFY=1`` the trace runs regardless and
        a lying ref is recorded as ``misses{cause=stale_ref}`` and
        replaced. ``info`` (the ``jit.program`` span's fields) is
        filled in."""
        info = {} if info is None else info
        ref_key = None
        failed_key = None
        if signature is not None:
            ref_key = _signature_key(signature, extra)
            exe_key = self._read_ref(ref_key)
            if exe_key is not None and not _verify_mode():
                b0 = self._moved_bytes()
                exe = self.load(exe_key, label=label)
                if exe is not None:
                    info.update(source="store",
                                bytes=self._moved_bytes() - b0)
                    return exe
                # entry vanished/corrupt under the ref (miss recorded
                # by load): re-derive everything through the traced path
                failed_key = exe_key
        lowered = _lower(lower_fn, info)
        true_key = self.key_for(lowered, extra=extra)
        if ref_key is not None and _verify_mode():
            stored = self._read_ref(ref_key)
            if stored is not None and stored != true_key:
                self._miss("stale_ref")
                monitor.record_swallowed(
                    f"jit.compile_cache.stale_ref[{label}]",
                    RuntimeError(f"manifest {ref_key[:12]} pointed at "
                                 f"{stored[:12]}, program is "
                                 f"{true_key[:12]}"))
        # when the ref's target just failed and IS this program's key,
        # skip the second lookup — one corruption must count one miss,
        # not corrupt+absent
        exe = self._load_or_compile(true_key, lowered, label, info,
                                    load=true_key != failed_key)
        if ref_key is not None:
            self._write_ref(ref_key, true_key)
        return exe

    def refs(self) -> List[str]:
        """Sorted manifest-entry paths."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(os.path.join(self.root, n) for n in names
                      if n.endswith(REF_SUFFIX))

    def clear(self):
        for path in self.entries() + self.refs():
            try:
                os.remove(path)
            except OSError:
                pass

    def __repr__(self):
        return (f"ExecutableStore({self.root!r}, entries={len(self)}, "
                f"stats={self.stats})")


def compile_or_load(lowered, *, store: Optional[ExecutableStore] = None,
                    extra: Optional[dict] = None, label: str = ""):
    """Compile ``lowered`` through ``store`` (default: the
    process-default store; with no store active this is exactly
    ``lowered.compile()``)."""
    store = store if store is not None else default_store()
    with flight_recorder.span("jit.program", label=label) as sp:
        info = {}
        if store is None:
            exe = _compile(lowered, info)
        else:
            exe = store.get_or_compile(lowered, extra=extra, label=label,
                                       info=info)
        sp.set(**info)
    return exe


def build_or_load(signature: Optional[dict], lower_fn, *,
                  store: Optional[ExecutableStore] = None,
                  extra: Optional[dict] = None, label: str = ""):
    """Traceless variant of :func:`compile_or_load`: on a manifest hit
    ``lower_fn`` is never called (zero traces, zero compiles). With no
    store active this is ``lower_fn().compile()``."""
    store = store if store is not None else default_store()
    with flight_recorder.span("jit.program", label=label) as sp:
        info = {}
        if store is None:
            exe = _compile(_lower(lower_fn, info), info)
        else:
            exe = store.get_or_build(signature, lower_fn, extra=extra,
                                     label=label, info=info)
        sp.set(**info)
    return exe
