"""The chips a run uses, their published peaks, and their memory."""
from __future__ import annotations

import json
import os

#: exit code of a run that finds no TPU, or too few chips
NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int):
    """The first `chips` TPU devices; raises NoChip otherwise. Never
    falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoChip(f"JAX found no device: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, from chipbench/peaks.json. A device
    that is not in the table is an error, not a default."""
    path = os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}")
    return table[device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip since the process began."""
    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in devs)
