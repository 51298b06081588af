"""The chips a cell runs on: found, checked against the peaks table, and
described for the result line."""

from .peaks import peaks


class NoChip(RuntimeError):
    """JAX finds no accelerator, too few of them, or one with no peaks."""


def require(n_chips: int, allow_cpu: bool = False) -> list:
    import jax
    devices = jax.devices()
    if not allow_cpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX finds {devices[0].platform} devices")
        try:
            peaks(devices[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from e
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips; JAX finds "
                     f"{len(devices)}")
    return devices[:n_chips]


def mesh(devices):
    """The (data=1, model=n) mesh over exactly the cell's n devices, as the
    program's launcher lays one over a host's (`--mesh host`); on one
    device, the launcher's one-device mesh."""
    from repro.launch.mesh import make_auto_mesh
    return make_auto_mesh((1, len(devices)), ("data", "model"),
                          devices=devices)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def describe(devices, memory_peak: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}
