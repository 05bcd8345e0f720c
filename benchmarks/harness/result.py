"""The device as JAX reports it, and the one line the driver reads."""

import json
import sys
import time

LAST_LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def log(*parts) -> None:
    """Everything but the result goes to earlier lines."""
    print("bench:", *parts, flush=True)


def mark(run, what: str) -> None:
    """Where set-up time goes: seconds since the process started."""
    log(f"t+{time.monotonic() - run.process_start:.1f} s: {what}")


def require_device(platform: str, chips: int) -> dict:
    """The devices this cell asked for, or no result: a cell that names
    ``tpu`` never falls back to the CPU."""
    import jax
    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != platform:
        raise SystemExit(f"the cell needs platform {platform!r}; JAX "
                         f"found {found}")
    if found["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); JAX found "
                         f"{found}")
    return found


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest of ``devices``: the allocator's peak of
    live arrays plus the peak the runtime reserved for running programs'
    temporaries, which ``peak_bytes_in_use`` leaves out on the TPU (ResNet-50
    at 128 images: 0.8 GB in use, 4.5 GB reserved, PERF.md).  0 where the
    backend keeps no such statistics (the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, breakdown=None) -> None:
    """Print the result as the last line of standard output."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
