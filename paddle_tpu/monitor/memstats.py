"""Memory gauges: what is HBM (or host RAM on the CPU backend) holding.

All gauges are lazy (`Gauge.set_fn`): they walk `jax.live_arrays()` /
query PJRT `memory_stats()` only when an exporter reads them, never on
the training hot path.
"""
from __future__ import annotations


def _live_arrays():
    import jax

    try:
        return jax.live_arrays()
    except Exception:
        return []


def live_array_bytes() -> int:
    total = 0
    for a in _live_arrays():
        try:
            if a.is_deleted():
                continue
            total += a.nbytes
        except Exception:
            pass
    return total


def live_array_count() -> int:
    n = 0
    for a in _live_arrays():
        try:
            if not a.is_deleted():
                n += 1
        except Exception:
            pass
    return n


def _device_stat(device_index: int, key: str):
    """One number of a device's PJRT `memory_stats()`; None where the backend
    (e.g. XLA:CPU) exposes none."""
    import jax

    try:
        stats = jax.local_devices()[device_index].memory_stats()
        return stats.get(key) if stats else None
    except Exception:
        return None


def device_bytes_in_use(device_index: int = 0) -> float:
    """PJRT allocator's bytes_in_use for one device; NaN where the backend
    exposes no memory_stats."""
    in_use = _device_stat(device_index, "bytes_in_use")
    return float("nan") if in_use is None else float(in_use)


def device_bytes_limit(device_index: int = 0):
    """PJRT allocator's bytes_limit for one device: the memory a program on it
    can have; None where the backend exposes no memory_stats."""
    limit = _device_stat(device_index, "bytes_limit")
    return int(limit) if limit else None


def register_memory_gauges(mon):
    """Install the lazy memory gauges on a Monitor (idempotent)."""
    mon.gauge("memory.live_array_bytes").set_fn(live_array_bytes)
    mon.gauge("memory.live_array_count").set_fn(live_array_count)
    mon.gauge("memory.device_bytes_in_use").set_fn(device_bytes_in_use)
    return mon
