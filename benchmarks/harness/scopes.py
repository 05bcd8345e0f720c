"""From the names the program writes into its compiled step and into the
profiler's host plane to time by phase, part and scope.

The program names its step from inside (``horovod_tpu``): ``jax.named_scope``
puts ``hvd::optimizer``, ``hvd::batch_norm``, ``hvd::sync_bn_stats``,
``hvd::allreduce[::<name>]`` and the model's parts (``stem``, ``max_pool``,
``stage1`` .. ``stage4``, ``head``) into the ``op_name`` of every operation
traced under them, JAX adds ``jvp(`` and ``transpose(jvp(`` for the forward
and the backward pass, and ``hvd.shard_step`` wraps each call in a
``jax.profiler.TraceAnnotation`` named ``hvd::shard_step::<function>`` with
the call's index as ``step``.  A device event carries only the HLO
instruction's name, so the ``op_name`` comes from the compiled step's text
(``op_names``).  A fusion is attributed whole to its own instruction's
``op_name``, which XLA takes from the fusion's root: an approximation.

Like ``trace.py`` the arithmetic works on plain tuples, so that it can be
checked on hand-built ones; ``host_spans`` is the only part that knows the
file (the device planes come through ``trace.load``), and ``table`` the
only part that knows a run.  With a program that writes no such name (the
parent of the PR that added them) the phases still split, since those
names are JAX's, the scopes' metrics are absent and there is no host span;
nothing raises.
"""

import bisect
import glob
import hashlib
import os
import re
import statistics
import time

from . import result
from . import trace as tracing

HOST_PLANE = re.compile(r"^/host:")
SPAN = "hvd::"
STEP_SPAN = "hvd::shard_step::"
PARTS = ("stem", "max_pool", "stage1", "stage2", "stage3", "stage4", "head")
PHASES = ("forward", "backward", "optimizer", "other", "unscoped")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
ALL_REDUCE = re.compile(r"^(.*?)\s+all-reduce(?:-start)?\(")
ARRAY = re.compile(r"\b(?:[a-z]+(\d+)[a-z0-9]*|pred)\[([\d,]*)\]")


# -- the compiled step's text -------------------------------------------------

def parse(hlo_text: str):
    """``(names, inside, calls)`` of the compiled text: every instruction's
    own ``op_name`` (``None`` without such metadata), the ``op_name``s of
    each computation's instructions in order, and each fusion's fused
    computation."""
    names, inside, calls, computation = {}, {}, {}, None
    for line in hlo_text.split("\n"):
        m = INSTRUCTION.match(line)
        if m:
            found = OP_NAME.search(m.group(2))
            names[m.group(1)] = found.group(1) if found else None
            inside.setdefault(computation, []).append(names[m.group(1)])
            called = CALLS.search(m.group(2))
            if called and " fusion(" in m.group(2):
                calls[m.group(1)] = called.group(1)
        else:
            opened = COMPUTATION.match(line)
            if opened:
                computation = opened.group(1)
    return names, inside, calls


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` for every instruction of the compiled
    text, ``None`` for one without such metadata.  A fusion counts whole
    for its own ``op_name``, which is its root's; where that names no phase
    (the root is the job's ``optax.apply_updates`` add, outside every
    scope, or XLA made the fusion and gave it no metadata) it takes the
    ``op_name`` nearest the root inside the fused computation that does."""
    names, inside, calls = parse(hlo_text)
    for fusion, called in calls.items():
        if classify(names[fusion])["phase"] in ("other", "unscoped"):
            names[fusion] = next(
                (n for n in reversed(inside.get(called, []))
                 if classify(n)["phase"] not in ("other", "unscoped")),
                names[fusion])
    return names


def scopes_held(hlo_text: str) -> dict:
    """``{instruction name: the hvd:: scopes in its own op_name and, for a
    fusion, in any op_name inside it}``, for instructions that hold one.
    XLA fuses across the program's scopes (batch norm's reductions into
    the convolutions, the optimizer's update into the weight gradients'):
    time by a fusion's root says how much a scope runs on its own, this
    says how much of the step's time is in kernels it is part of."""
    names, inside, calls = parse(hlo_text)
    held = {}
    for inst, own in names.items():
        found = {step for op_name in [own] + inside.get(calls.get(inst), [])
                 if op_name for step in op_name.split("/")
                 if step.startswith(SPAN)}
        if found:
            held[inst] = found
    return held


def stripped(hlo_text: str) -> str:
    """The compiled text without what names alone change: ``metadata={...}``
    and the tables of files, functions and stack frames it points into.
    Two programs that differ in names only give the same text."""
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", hlo_text)
    head, tables, rest = text.partition("\nFileNames\n")
    if tables:
        first = re.search(r"\n\n(?=\S.*\{\n)", rest)
        rest = rest[first.end():] if first else rest
    return head + "\n" + rest


def classify(op_name) -> dict:
    """Phase, part and flags of one ``op_name`` (``None``: the instruction
    has none).  ``loss`` is what is differentiated outside the model's
    parts."""
    if not op_name:
        return {"phase": "unscoped", "part": "-", "batch_norm": False,
                "sync_bn_stats": False}
    if "hvd::optimizer" in op_name:
        phase = "optimizer"
    elif "transpose(jvp(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "other"
    steps = op_name.split("/")
    part = next((p for p in PARTS if p in steps),
                "loss" if phase in ("forward", "backward") else "-")
    return {"phase": phase, "part": part,
            "batch_norm": "hvd::batch_norm" in steps,
            "sync_bn_stats": "hvd::sync_bn_stats" in steps}


def collective_scope(op_name) -> str:
    """Whose collective it is.  Sync batch norm has two: the statistics'
    all-reduce, under ``hvd::sync_bn_stats``, and in the backward pass the
    all-reduce of the folded scale's and offset's cotangents, which the
    statistics' and the parameters' gradients both wait for: with varying-
    axes tracking the transpose of the statistics' ``psum`` is no
    collective, the backward one is the transpose of the cast where the
    replicated scale meets the activations, under ``hvd::batch_norm``.
    Every other collective of the backward pass reduces gradients."""
    c = classify(op_name)
    if c["sync_bn_stats"]:
        return f"sync_bn_stats {c['phase']}"
    if c["batch_norm"] and c["phase"] == "backward":
        return "sync_bn backward"
    if c["phase"] == "backward":
        return "gradients"
    explicit = [s for s in (op_name or "").split("/")
                if s.startswith(SPAN) and s != "hvd::optimizer"]
    return explicit[-1] if explicit else c["phase"]


def array_bytes(shape_text: str) -> int:
    """Bytes of every array in an HLO shape (a tuple's members summed)."""
    total = 0
    for bits, dims in ARRAY.findall(shape_text):
        n = int(bits or 8)            # ``pred`` names no width: a byte
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n // 8
    return total


def all_reduces(hlo_text: str):
    """``[(instruction name, op_name or None, bytes)]`` of every
    ``all-reduce`` and ``all-reduce-start`` of the compiled text; bytes
    are those of its result, which has the shape of its operands."""
    found = []
    for line in hlo_text.split("\n"):
        m = INSTRUCTION.match(line)
        if not m:
            continue
        shape = ALL_REDUCE.match(m.group(2))
        if shape:
            name = OP_NAME.search(m.group(2))
            found.append((m.group(1), name.group(1) if name else None,
                          array_bytes(shape.group(1))))
    return found


def all_reduce_bytes_by_scope(hlo_text: str) -> dict:
    """``{scope: [count, bytes]}`` over ``all_reduces``."""
    by_scope = {}
    for _, op_name, nbytes in all_reduces(hlo_text):
        entry = by_scope.setdefault(collective_scope(op_name), [0, 0])
        entry[0] += 1
        entry[1] += nbytes
    return by_scope


# -- intervals ----------------------------------------------------------------

def exposed(collective_intervals, other_intervals) -> int:
    """Length of the union of ``collective_intervals`` that no interval of
    ``other_intervals`` covers: ``(start, end)`` pairs of one device."""
    others = tracing.union(other_intervals)
    total, j = 0, 0
    for start, end in tracing.union(collective_intervals):
        at = start
        while j < len(others) and others[j][1] <= at:
            j += 1
        k = j
        while k < len(others) and others[k][0] < end:
            if others[k][0] > at:
                total += others[k][0] - at
            at = max(at, others[k][1])
            k += 1
        if at < end:
            total += end - at
    return total


def join(spans, modules):
    """Lead of dispatch over execution, one figure per call: the start of
    the k-th execution of a program (``modules``: one device's ``XLA
    Modules`` events, ``jit_<function>(<fingerprint>)``) minus the start of
    the k-th ``hvd::shard_step::<function>`` span (``spans``: ``(name,
    start, duration, step)``, ordered by ``step`` where the program wrote
    one).  Holds where the trace starts with nothing in flight."""
    leads = []
    functions = sorted({name[len(STEP_SPAN):] for name, *_ in spans
                        if name.startswith(STEP_SPAN)})
    for function in functions:
        calls = sorted(
            (s for s in spans if s[0] == STEP_SPAN + function),
            key=lambda s: (s[3] is None, s[3] or 0, s[1]))
        runs = sorted(m[1] for m in modules
                      if re.sub(r"\(.*$", "", m[0]) == "jit_" + function)
        leads += [run - call[1] for call, run in zip(calls, runs)]
    return leads


def gaps_between_programs(op_intervals, modules, spans, window):
    """``{host span or "no hvd span": idle nanoseconds}`` over the idle
    gaps of one device that start outside every program: the first
    ``hvd::`` span open on the host at some time in the gap says what the
    host was doing while the device waited."""
    w0, w1 = window
    edges = [(w0, w0)] + tracing.union(op_intervals) + [(w1, w1)]
    programs = tracing.union(tracing.spans(modules))
    starts = [start for start, _ in programs]
    by_span = {}
    for (_, idle_from), (idle_to, _) in zip(edges, edges[1:]):
        if idle_to <= idle_from:
            continue
        # Most gaps are a few nanoseconds between two operations of one
        # program: find the program by bisection, not by a scan.
        i = bisect.bisect_right(starts, idle_from) - 1
        if i >= 0 and idle_from < programs[i][1]:
            continue
        key = next((name for name, s, d, _ in spans
                    if s < idle_to and s + d > idle_from), "no hvd span")
        by_span[key] = by_span.get(key, 0) + (idle_to - idle_from)
    return by_span


# -- the file -----------------------------------------------------------------

def host_spans(trace_dir: str):
    """The ``hvd::`` events of the host planes of the newest trace under
    ``trace_dir`` as ``(name, start, duration, step)``, sorted by start, on
    the clock of the device planes (``step`` is ``None`` where the event
    has no such stat)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    spans = []
    for plane in ProfileData.from_file(files[-1]).planes if files else ():
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN):
                    step = dict(e.stats).get("step")
                    spans.append((e.name, int(e.start_ns),
                                  int(e.duration_ns),
                                  None if step is None else int(step)))
    return sorted(spans, key=lambda s: s[1])


# -- one run ------------------------------------------------------------------

def instruction_of(event_name: str) -> str:
    return tracing.short_name(event_name).split(" ")[0]


def is_collective(event_name: str) -> bool:
    """By the instruction's opcode, which a device event's name holds
    (``%psum_invariant.7 = f32[129]{0} all-reduce(...)``): an all-reduce
    that XLA did not combine with another keeps the name of JAX's
    primitive, so the instruction's name does not say."""
    opcode = OPCODE.search(event_name.partition(" = ")[2])
    return bool(tracing.COLLECTIVE.search(
        opcode.group(1) if opcode else event_name))


def reduce(devices: dict, spans, names: dict, held=None) -> dict:
    """The figures the readers cut their metrics from, averaged over the
    devices; times in seconds.  ``names`` is ``op_names`` of the step,
    ``held`` its ``scopes_held``."""
    held = held or {}
    w0, w1 = tracing.window_of(devices)
    n = len(devices)
    ns = 1e-9 / n
    cells, by_phase, unnamed = {}, dict.fromkeys(PHASES, 0), {}
    exposed_by, in_flight, leads, gaps, holding = {}, {}, [], {}, {}
    op_seconds = batch_norm = busy = unmatched = 0
    read = {}   # event name -> what its text says, read once a name

    def reading(name):
        if name not in read:
            inst = instruction_of(name)
            c = classify(names.get(inst))
            read[name] = (inst, c, inst in names,
                          collective_scope(names.get(inst))
                          if is_collective(name) else None)
        return read[name]

    for dev in devices.values():
        compute, collectives = [], {}
        for name, s, d in dev["ops"]:
            inst, c, matched, scope = reading(name)
            if scope is None:
                compute.append((s, s + d))
            else:
                collectives.setdefault(scope, []).append((s, s + d))
            unmatched += 0 if matched else d
            for scope in held.get(inst, ()):
                holding[scope] = holding.get(scope, 0) + d
            by_phase[c["phase"]] += d
            op_seconds += d
            batch_norm += d if c["batch_norm"] else 0
            key = (c["phase"], c["part"], c["batch_norm"])
            cells[key] = cells.get(key, 0) + d
            if c["phase"] in ("other", "unscoped"):
                unnamed[c["phase"], inst] = \
                    unnamed.get((c["phase"], inst), 0) + d
        for name, s, d in dev["async"]:
            scope = reading(name)[3]
            if scope is not None:
                collectives.setdefault(scope, []).append((s, s + d))
        groups = dict(collectives)
        groups["all"] = [i for group in collectives.values() for i in group]
        groups["sync_bn"] = [i for scope, group in collectives.items()
                             if scope.startswith("sync_bn") for i in group]
        compute = tracing.union(compute)      # merged once, for all groups
        for scope, group in groups.items():
            exposed_by[scope] = exposed_by.get(scope, 0) + \
                exposed(group, compute)
            in_flight[scope] = in_flight.get(scope, 0) + \
                tracing.covered(group)
        op_intervals = tracing.spans(dev["ops"])
        busy += tracing.covered(op_intervals)
        leads += join(spans, dev["modules"])
        for key, idle in gaps_between_programs(
                op_intervals, dev["modules"], spans, (w0, w1)).items():
            gaps[key] = gaps.get(key, 0) + idle
    steps = [s for s in spans if s[0].startswith(STEP_SPAN)]

    def seconds(by_key):
        return {k: v * ns for k, v in by_key.items()}

    return {
        "named": {step for op_name in filter(None, names.values())
                  for step in op_name.split("/") if step.startswith(SPAN)},
        "devices": n, "window_s": (w1 - w0) * 1e-9, "busy_s": busy * ns,
        "op_s": op_seconds * ns, "by_phase": seconds(by_phase),
        "batch_norm_s": batch_norm * ns, "unmatched_s": unmatched * ns,
        "cells": seconds(cells), "holding": seconds(holding),
        "unnamed": seconds(unnamed), "exposed_s": seconds(exposed_by),
        "in_flight_s": seconds(in_flight),
        "host_dispatch_ms": [s[2] * 1e-6 for s in steps],
        "leads_ms": [x * 1e-6 for x in leads],
        "gaps_between_programs": seconds(gaps),
    }


def made_once(run, what: str, make):
    """``make()``, kept on the run under ``what``: the readers are separate
    modules that are handed the same run."""
    kept = run.__dict__.setdefault("scopes", {})
    if what not in kept:
        kept[what] = make()
    return kept[what]


def table(run):
    """``reduce`` of this run's trace, made once; ``None`` where the trace
    holds no device plane (the CPU rehearsal) or there was no trace."""
    def make():
        trace_dir = run.results.get("trace_dir")
        devices = tracing.load(trace_dir) if trace_dir else {}
        return reduce(devices, host_spans(trace_dir),
                      op_names(hlo_text(run)),
                      scopes_held(hlo_text(run))) if devices else None
    return made_once(run, "table", make)


def hlo_text(run) -> str:
    """The compiled step's text with **this program's** names, made once a
    run.  JAX leaves metadata out of the key of its persistent compile
    cache, so a step found there comes back with the ``op_name`` of
    whichever checkout compiled it first (the parent's, which has no
    ``hvd::`` scope, where both ran on one machine), and the jitted step
    keeps that executable in memory.  So the step is traced, lowered and
    compiled anew, past both: the instructions and their names are the
    same (the text without metadata is identical; ``log_table`` prints the
    time in operations the text does not hold), the metadata is this
    program's.  Nothing of the run is measured after this."""
    def make():
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            t0 = time.monotonic()
            text = run.results["hlo_text"]()
            result.log(f"scopes: compiled the step anew in "
                       f"{time.monotonic() - t0:.1f} s")
            return text
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
    return made_once(run, "text", make)


def share(run, seconds_of, needs=None, of="op_s"):
    """``100 * seconds_of(table) / table[of]``; ``None`` with no device
    trace, or where the program wrote no scope ``needs`` (the parent of
    the PR that added them).  ``op_s`` is the sum of the operations'
    durations, which is the busy time where operations do not overlap
    (``log_table`` prints both)."""
    t = table(run)
    if t is None or (needs is not None and needs not in t["named"]):
        return None
    return 100.0 * seconds_of(t) / t[of]


def log_all_reduces(run) -> None:
    """The step's all-reduces by whose they are, into the log: a count,
    so the CPU rehearsal has it too."""
    for scope, (count, nbytes) in sorted(
            all_reduce_bytes_by_scope(hlo_text(run)).items()):
        result.log(f"scopes: all-reduce {scope}: {count} operations, "
                   f"{nbytes} bytes")


def log_table(run) -> None:
    """The whole table the readers are cut from, into the log."""
    t = table(run)
    log = result.log
    if t is None:
        return
    total = t["op_s"]
    log(f"scopes: {t['devices']} device(s), window {t['window_s']:.4f} s, "
        f"busy {t['busy_s']:.4f} s, operations' durations summed "
        f"{total:.4f} s")
    for phase in PHASES:
        log(f"scopes: phase {phase} {t['by_phase'][phase]:.5f} s "
            f"{100 * t['by_phase'][phase] / total:.2f} %")
    log(f"scopes: the step's text without metadata: sha256 "
        f"{hashlib.sha256(stripped(hlo_text(run)).encode()).hexdigest()}")
    log(f"scopes: operations the step's text does not hold "
        f"{t['unmatched_s']:.5f} s; scopes found {sorted(t['named'])}")
    log(f"scopes: batch_norm (forward and backward) "
        f"{t['batch_norm_s']:.5f} s {100 * t['batch_norm_s'] / total:.2f} %")
    for scope, seconds in sorted(t["holding"].items()):
        log(f"scopes: operations that hold an instruction under {scope} "
            f"{seconds:.5f} s {100 * seconds / total:.2f} %")
    for (phase, part, bn), seconds in tracing.top(t["cells"], 20):
        log(f"scopes: {phase} x {part} x "
            f"{'batch_norm' if bn else 'rest'} {seconds:.5f} s "
            f"{100 * seconds / total:.2f} %")
    for (phase, inst), seconds in tracing.top(t["unnamed"], 12):
        log(f"scopes: {phase} instruction {inst} {seconds:.5f} s "
            f"{100 * seconds / total:.2f} %")
    for scope, seconds in sorted(t["exposed_s"].items(),
                                 key=lambda kv: -kv[1]):
        log(f"scopes: exposed collective time, {scope}: {seconds:.5f} s "
            f"{100 * seconds / t['window_s']:.3f} % of the window, of "
            f"{t['in_flight_s'][scope]:.5f} s in flight")
    log_all_reduces(run)
    if t["host_dispatch_ms"]:
        log(f"scopes: {len(t['host_dispatch_ms'])} hvd::shard_step spans, "
            f"mean {statistics.fmean(t['host_dispatch_ms']):.4f} ms, "
            f"median {statistics.median(t['host_dispatch_ms']):.4f} ms")
    if t["leads_ms"]:
        log(f"scopes: lead of dispatch over execution: median "
            f"{statistics.median(t['leads_ms']):.3f} ms, min "
            f"{min(t['leads_ms']):.3f} ms, max {max(t['leads_ms']):.3f} ms "
            f"over {len(t['leads_ms'])} executions; "
            f"{sum(1 for x in t['leads_ms'] if x < 0)} started before "
            f"their span")
    for span, seconds in tracing.top(t["gaps_between_programs"], 8):
        log(f"scopes: idle between programs, {span}: {seconds:.6f} s")
