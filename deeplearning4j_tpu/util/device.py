"""What this process actually runs on, as jax reports it.

Every entry point that produces a device number (``chip_smoke.py``, the
on-chip bench scripts) states the device in its output and refuses to
run on anything else: a CPU or interpreter run is never written under
the name of a device metric.
"""

from __future__ import annotations


def describe() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def banner(dev: dict) -> str:
    """The one spelling of a device in program output."""
    return (f"platform: {dev['platform']} device_kind: {dev['kind']} "
            f"devices: {dev['count']}")


def require_tpu(what: str) -> dict:
    """:func:`describe`, or ``RuntimeError`` when the default backend is
    not a TPU. ``what`` names the refusing entry point in the message.
    Sets no platform itself: the caller's environment decides, this
    only refuses to mislabel the result."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"{what} measures the chip and found none: jax reports "
            f"platform={dev['platform']!r} kind={dev['kind']!r} "
            f"count={dev['count']}. It does not fall back to the CPU.")
    return dev
