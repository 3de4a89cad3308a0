"""The device check and device record every run starts from."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_device(want: int) -> dict:
    """Refuse to run anywhere but on ``want`` TPU chips with compiled
    (not interpreted) Pallas kernels; return the device record."""
    import jax

    from repro import kernels
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {d0.platform})")
    if kernels.INTERPRET:
        raise NoChip("Pallas kernels are in interpret mode on a TPU")
    if len(devices) < want:
        raise NoChip(f"the cell needs {want} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": want}


def memory_peak_bytes(devices) -> dict:
    """Peak device memory on the fullest chip, as the backend reports it
    (0 where it reports nothing).  ``memory_peak_bytes`` is the larger of
    the allocator's peak in use and its peak reserved: a compiled
    program's temporaries are reserved for it and do not show in use.
    Both readings are kept beside it."""
    stats = [d.memory_stats() or {} for d in devices]
    in_use = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    reserved = max((s.get("peak_bytes_reserved", 0) for s in stats),
                   default=0)
    return {"memory_peak_bytes": int(max(in_use, reserved)),
            "memory_peak_bytes_in_use": int(in_use),
            "memory_peak_bytes_reserved": int(reserved)}
