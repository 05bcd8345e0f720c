"""From a profiler trace (``.xplane.pb``) to busy and idle time, time by
operation name, and idle gaps.

The arithmetic works on plain tuples, so that it can be checked on a
hand-built trace; ``load`` is the only part that knows the file.  A TPU
trace has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
holds one event per executed operation and whose line ``XLA Modules``
holds one event per executed program (the name of the jitted function).
Times are nanoseconds on the profiler's clock.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous operations
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def start(trace_dir: str) -> None:
    """Start the profiler into a fresh ``trace_dir``.  The Python tracer
    stays off: it slows the very host loop whose gaps the trace is to show."""
    import shutil
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(trace_dir: str) -> dict:
    """``{plane name: {"ops": [(name, start, duration)], "modules":
    [...]}}`` for every device plane of the newest trace under
    ``trace_dir``; empty where the trace holds no device plane (the CPU)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return {}
    data = ProfileData.from_file(files[-1])
    devices = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE, ASYNC_LINE):
                lines[line.name] = [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events]
        if lines.get(OPS_LINE):
            devices[plane.name] = {"ops": lines[OPS_LINE],
                                   "modules": lines.get(MODULES_LINE, []),
                                   "async": lines.get(ASYNC_LINE, [])}
    return devices


def short_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO instruction,
    ``%fusion.12 = bf16[...] fusion(...), kind=...``: keep the instruction's
    name, and the kernel's for a Pallas call."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    name = head.lstrip("%")
    kernel = re.search(r'kernel_name\s*=\s*"?([\w.]+)', rest)
    return f"{name} {kernel.group(1)}" if kernel else name


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def covered(intervals) -> int:
    return sum(end - start for start, end in union(intervals))


def spans(events, match=None):
    return [(s, s + d) for name, s, d in events
            if match is None or match(name)]


def window_of(devices: dict):
    """``(start, end)`` of the traced window: first operation's start to
    last operation's end over all devices."""
    starts = [s for dev in devices.values() for _, s, _ in dev["ops"]]
    ends = [s + d for dev in devices.values() for _, s, d in dev["ops"]]
    return min(starts), max(ends)


def program_of(modules, starts, t: int) -> str:
    """Where ``t`` falls among this device's programs (``modules`` sorted
    by start, ``starts`` their start times): inside one, or after which."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return "before the first program"
    name, start, duration = modules[i]
    short = re.sub(r"\(.*$", "", name)
    return f"inside {short}" if t < start + duration else f"after {short}"


def reduce(devices: dict) -> dict:
    """The figures the metrics read, averaged over the devices:

    ``window_s``, ``busy_s`` (union of operation intervals), ``by_name``
    (seconds by operation name with trailing ``.<n>`` suffixes kept, summed
    over a device and averaged over devices), ``collective_s`` (union of
    collective operations' intervals, with the start-to-done spans of the
    asynchronous ones), ``gaps`` (idle seconds by where the
    gap fell: inside a program, or after which program; nothing in the
    program says what the host did there, so they are ``unattributed``).
    """
    if not devices:
        return {}
    w0, w1 = window_of(devices)
    n = len(devices)
    busy = collective = 0
    by_name, gaps = {}, {}
    for dev in devices.values():
        ops = [(short_name(name), s, d) for name, s, d in dev["ops"]]
        merged = union(spans(ops))
        busy += sum(e - s for s, e in merged)
        in_flight = [(short_name(name), s, d)
                     for name, s, d in dev.get("async", [])]
        collective += covered(spans(ops + in_flight, COLLECTIVE.search))
        for name, _, d in ops:
            by_name[name] = by_name.get(name, 0) + d
        edges = [(w0, w0)] + merged + [(w1, w1)]
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for (_, end), (start, _) in zip(edges, edges[1:]):
            if start > end:
                where = program_of(modules, starts, end)
                gaps[where] = gaps.get(where, 0) + (start - end)
    ns = 1e-9
    return {
        "devices": n,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / n * ns,
        "collective_s": collective / n * ns,
        "by_name": {k: v / n * ns for k, v in by_name.items()},
        "gaps": {f"unattributed, {k}": v / n * ns for k, v in gaps.items()},
    }


def top(table: dict, k: int = 10):
    return [[name, seconds] for name, seconds in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def breakdown(reduced: dict) -> dict:
    return {"device_ops": top(reduced["by_name"]),
            "idle_gaps": top(reduced["gaps"])}


def describe(trace_dir: str, limit: int = 12) -> str:
    """Planes, lines and a few events of a trace (with their stats for
    the first of each line and for custom calls), for reading one by hand."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = []
    for path in files[-1:]:
        out.append(f"{path} ({os.path.getsize(path)} bytes)")
        for plane in ProfileData.from_file(path).planes:
            out.append(f"plane {plane.name!r}")
            for line in plane.lines:
                events = list(line.events)
                out.append(f"  line {line.name!r}: {len(events)} events")
                customs = [e for e in events if "custom-call" in e.name]
                for i, e in enumerate(events[:limit] + customs[:3]):
                    out.append(f"    {e.name!r} start={e.start_ns} "
                               f"dur={e.duration_ns}")
                    if i == 0 or i >= limit:
                        out.append(f"      stats {dict(e.stats)!r}"[:1500])
    return "\n".join(out)
