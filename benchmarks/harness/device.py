"""The device line of a result, and the look for a chip."""

from __future__ import annotations

import os
import sys


def rehearsing() -> bool:
    """An explicit ``JAX_PLATFORMS=cpu`` asks for the CPU rehearsal: tiny
    sizes, every line marked, no result line, exit code 2."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require(chips: int, rehearsal: bool) -> list:
    """The devices the cell runs on, or exit 1 with no result line."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        raise SystemExit(1)
    if rehearsal:
        return devices[:chips]
    if devices[0].platform != "tpu":
        print(f"JAX found platform {devices[0].platform!r}, not a TPU; the "
              "benchmark does not fall back", file=sys.stderr)
        raise SystemExit(1)
    if len(devices) < chips:
        print(f"the cell asks for {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        raise SystemExit(1)
    return devices[:chips]


def line(devices: list) -> dict:
    """``platform``, ``kind``, ``count`` and the peak bytes on the fullest
    chip, as JAX reports them.  This runtime counts live arrays under
    ``peak_bytes_in_use`` and what a running program takes for its
    temporaries under ``peak_bytes_reserved`` (PERF.md section 4); the
    arrays a program works on are live while it runs, so the chip's peak
    is their sum.  Both parts stand beside it."""
    stats = [d.memory_stats() or {} for d in devices]
    in_use, reserved = max(
        ((int(s.get("peak_bytes_in_use", 0)),
          int(s.get("peak_bytes_reserved", 0))) for s in stats), key=sum)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": in_use + reserved,
            "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved}
