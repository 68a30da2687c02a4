"""paddle.device analog namespace, including the CUDA-parity memory
API (paddle.device.cuda.{memory_allocated,max_memory_allocated,
memory_reserved,max_memory_reserved} over memory/stats.h) backed by the
PJRT allocator's `memory_stats()`, with a `jax.live_arrays()` fallback
where the backend exposes none (CPU)."""
from ..core.device import (Place, current_place, device_count,  # noqa: F401
                           get_device, is_compiled_with_tpu, set_device,
                           synchronize)


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def _resolve(device):
    """None / 'tpu:N' / 'cpu' / int / jax.Device -> jax.Device."""
    import jax
    from ..core import device as core_device
    if device is None:
        return core_device.current_place().jax_device
    if isinstance(device, (str, int)):
        spec = device if isinstance(device, str) else \
            f"{core_device._parse(core_device.get_device())[0]}:{device}"
        plat, idx = core_device._parse(spec)
        devs = [d for d in jax.devices() if d.platform == plat]
        if idx >= len(devs):
            raise ValueError(f"device {device!r} out of range "
                             f"({len(devs)} {plat} devices)")
        return devs[idx]
    return device


def memory_stats(device=None):
    """Per-device allocator stats (≈ paddle.device.cuda memory APIs over
    memory/stats.h). `device` may be None (the set_device()-selected
    device), a 'tpu:N'/'cpu' string, an int index, or a jax device.
    Returns the PJRT allocator stats dict, or {} when the backend
    doesn't expose them (CPU)."""
    stats = _resolve(device).memory_stats()  # None when backend lacks stats
    return dict(stats) if stats else {}


def _live_bytes(dev) -> int:
    """Fallback accounting: sum of live jax array footprints resident on
    `dev`. O(live arrays) — fine for the stats API, not a hot path."""
    import jax
    total = 0
    try:
        for a in jax.live_arrays():
            try:
                if dev in a.devices():
                    total += a.nbytes // max(len(a.devices()), 1)
            except Exception:
                continue
    except Exception:
        return 0
    return total


# High-water marks this process has observed per device, so the peak
# API works on backends without peak_bytes_in_use AND supports
# reset_peak_memory_stats. PJRT offers no reset, so a reset records the
# backend's peak at that moment (_PEAK_BASE); afterwards the backend
# value only counts again once it EXCEEDS that baseline (meaning a new
# high happened after the reset — this keeps intra-step transient peaks
# visible on stats backends even between polls).
_PEAK: dict = {}
_PEAK_BASE: dict = {}      # allocated: backend peak at last reset
_PEAK_RES: dict = {}       # reserved: tracked high-water
_PEAK_RES_BASE: dict = {}  # reserved: backend peak at last reset


def _devkey(dev) -> str:
    return f"{dev.platform}:{dev.id}"


def _observe(dev, current: int) -> int:
    key = _devkey(dev)
    if current > _PEAK.get(key, 0):
        _PEAK[key] = current
    from ..core import monitor
    if monitor.enabled:
        from ..core import device as core_device
        from ..core import metrics
        # the unlabeled gauge is the *current device's* track; queries
        # against other devices must not clobber it mid-trace
        if dev == core_device.current_place().jax_device:
            metrics.gauge("device.memory.allocated").set(current)
        else:
            metrics.gauge("device.memory.allocated", dev=key).set(current)
    return current


def memory_allocated(device=None) -> int:
    """Current bytes in use on the device (live-array accounting when
    the backend has no allocator stats)."""
    dev = _resolve(device)
    stats = dev.memory_stats()
    cur = int(stats.get("bytes_in_use", 0)) if stats else _live_bytes(dev)
    return _observe(dev, cur)


def _peak_of(key: str, tracked: int, backend_peak: int,
             base_map: dict) -> int:
    base = base_map.get(key)
    if base is None:
        return max(backend_peak, tracked)
    # after a reset, the backend peak is stale unless it has grown past
    # its value at reset time (i.e. a new high-water happened since)
    return max(tracked, backend_peak) if backend_peak > base else tracked


def max_memory_allocated(device=None) -> int:
    """Peak bytes allocated on the device since process start or the
    last reset_peak_memory_stats()."""
    dev = _resolve(device)
    stats = dev.memory_stats()
    cur = int(stats.get("bytes_in_use", 0)) if stats else _live_bytes(dev)
    _observe(dev, cur)
    key = _devkey(dev)
    tracked = _PEAK.get(key, cur)
    if stats:
        return _peak_of(key, tracked,
                        int(stats.get("peak_bytes_in_use", 0)), _PEAK_BASE)
    return tracked


def _reserved_from(stats: dict) -> int:
    for k in ("pool_bytes", "bytes_reserved"):
        if stats.get(k):
            return int(stats[k])
    return int(stats.get("bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes held by the allocator pool (≈ memory_reserved over
    STAT_GPU Reserved). PJRT reports pool/reserved bytes where the
    allocator is BFC; elsewhere reserved == allocated."""
    dev = _resolve(device)
    stats = dev.memory_stats()
    cur = _reserved_from(stats) if stats else _live_bytes(dev)
    key = _devkey(dev)
    if cur > _PEAK_RES.get(key, 0):
        _PEAK_RES[key] = cur
    return cur


def _backend_peak_reserved(stats: dict) -> int:
    for k in ("peak_pool_bytes", "peak_bytes_reserved"):
        if stats.get(k):
            return int(stats[k])
    return int(stats.get("peak_bytes_in_use", 0))


def max_memory_reserved(device=None) -> int:
    dev = _resolve(device)
    stats = dev.memory_stats()
    cur = memory_reserved(dev)
    key = _devkey(dev)
    tracked = _PEAK_RES.get(key, cur)
    if stats:
        return _peak_of(key, tracked, _backend_peak_reserved(stats),
                        _PEAK_RES_BASE)
    return tracked


def reset_max_memory_allocated(device=None) -> int:
    """Drop the device's ALLOCATED high-water mark to the current
    allocation and return it (paddle.device.cuda name; PJRT cannot
    reset its own peak, so the backend value is ignored until it
    exceeds its level at this reset). Also resets the
    `device.memory.allocated` gauge's peak in the metrics registry."""
    dev = _resolve(device)
    stats = dev.memory_stats()
    key = _devkey(dev)
    if stats:
        cur = int(stats.get("bytes_in_use", 0))
        _PEAK_BASE[key] = int(stats.get("peak_bytes_in_use", 0))
    else:
        cur = _live_bytes(dev)
        _PEAK_BASE[key] = 0
    _PEAK[key] = cur
    from ..core import device as core_device
    from ..core import metrics
    if dev == core_device.current_place().jax_device:
        metrics.gauge("device.memory.allocated").reset_peak()
    else:
        metrics.gauge("device.memory.allocated", dev=key).reset_peak()
    return cur


def reset_max_memory_reserved(device=None) -> int:
    """Drop the device's RESERVED high-water mark to the current pool
    size and return it (paddle.device.cuda name)."""
    dev = _resolve(device)
    stats = dev.memory_stats()
    key = _devkey(dev)
    if stats:
        cur = _reserved_from(stats)
        _PEAK_RES_BASE[key] = _backend_peak_reserved(stats)
    else:
        cur = _live_bytes(dev)
        _PEAK_RES_BASE[key] = 0
    _PEAK_RES[key] = cur
    return cur


def reset_peak_memory_stats(device=None) -> int:
    """Reset BOTH high-water marks (allocated and reserved) and return
    the current allocation — the whole-stats reset the torch-style name
    implies."""
    cur = reset_max_memory_allocated(device)
    reset_max_memory_reserved(device)
    return cur
