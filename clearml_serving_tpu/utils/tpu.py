"""Device identity and memory, as JAX reports them.

"The TPU" is ``platform == "tpu"`` and nothing else. These helpers touch the
backend, so only a process that owns the chip calls them (a chip belongs to
one process): the serving process reports them through the engine's
``health()`` block, the engine server through its own Prometheus gauges.
The statistics service never imports jax.
"""

from __future__ import annotations

from typing import Any, Dict, List


def device_identity() -> Dict[str, Any]:
    """``{platform, device_kind, device_count}`` of the default backend —
    the block every measurement and health report names its device by."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> Dict[str, Any]:
    """:func:`device_identity`, or ``SystemExit`` off-chip: a measurement
    path that finds no TPU fails — it never falls back to another backend."""
    ident = device_identity()
    if ident["platform"] != "tpu":
        raise SystemExit(
            "no TPU: jax reports platform {!r} ({}); this entry point "
            "measures on the chip only".format(
                ident["platform"], ident["device_kind"]
            )
        )
    return ident


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per local device ``{id, bytes_in_use, peak_bytes_in_use,
    bytes_limit}`` from ``device.memory_stats()``; a backend that reports
    none (CPU) contributes ``{id}`` only."""
    import jax

    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        row = {"id": dev.id}
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                row[key] = int(stats[key])
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if limit:
            row["bytes_limit"] = int(limit)
        out.append(row)
    return out
