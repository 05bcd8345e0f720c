"""The all-reduces a compiled step runs, each once, and what the
asynchronous ones cost on the core.

The TPU compiler runs an asynchronous all-reduce as an
``async_collective_fusion`` cut into pieces, each a fused computation of
its own: the start half ends in a custom call to ``AsyncCollectiveStart``,
the done half in one to ``AsyncCollectiveDone``, the pieces between ride
in the compute fusions the transfer hides behind.  Every piece holds a
copy of the ``all-reduce``, and the two halves are instructions of the
entry computation with opcode ``fusion`` (``async-collective-start.N``,
``async-collective-done.N``).  So a reader that matches ``all-reduce(``
anywhere in the text counts such an all-reduce once per piece, and one
that finds collectives in the device trace by opcode does not see it at
all (``layer_metrics/allreduce_ops.train.py``, ``allreduce_mb.train.py``,
``collective_share.train.py``, ``exposed_collective_share.train.py``,
``sync_bn_exposed_share.train.py``: they read a step's synchronous
all-reduces right, and stay as they are).  Here an all-reduce counts
where it runs: in the entry computation, or in a start half.  Whose it is
says its ``op_name`` (``scopes.collective_scope``).

The start and done pieces run on the core like any operation, with
nothing beside them: that time is exposed, as a synchronous all-reduce's
whole duration is.  Only the span between the two pieces hides behind
compute.

The arithmetic works on plain text and tuples, so that it can be checked
on hand-built ones; ``table`` is the only part that knows a run.  With a
program that has no asynchronous all-reduce (the parent of the PR that
brought them) the synchronous ones are read all the same, the asynchronous
readings are absent, and nothing raises.
"""

import collections
import re

from . import result, scopes
from . import trace as tracing

HALF = re.compile(r'custom_call_target="AsyncCollective(Start|Done)"')
DONE_OF = re.compile(r" all-reduce-done\(%?([\w.\-]+)")
PIECE = re.compile(r"^(.*?)start((?:\.\d+)?)$")
GRADIENTS = "gradients"

#: ``name`` is the instruction a synchronous all-reduce's device events
#: carry; ``start`` and ``done`` those of an asynchronous one's two pieces
#: (the two halves of a fusion, or ``all-reduce-start`` and its ``-done``).
AllReduce = collections.namedtuple("AllReduce",
                                   "scope nbytes name start done")


def all_reduces(hlo_text: str):
    """One ``AllReduce`` for every all-reduce the step runs; the copies in
    the other pieces of an asynchronous fusion are left out."""
    held, callers, dones, computation = {}, {}, {}, None
    for line in hlo_text.split("\n"):
        m = scopes.INSTRUCTION.match(line)
        if not m:
            opened = scopes.COMPUTATION.match(line)
            computation = opened.group(1) if opened else computation
            continue
        entry = held.setdefault(computation, {"half": None, "reduces": []})
        inst, text = m.groups()
        half = HALF.search(text)
        if half:
            entry["half"] = half.group(1).lower()
        shape = scopes.ALL_REDUCE.match(text)
        if shape:
            name = scopes.OP_NAME.search(text)
            entry["reduces"].append((
                scopes.collective_scope(name.group(1) if name else None),
                scopes.array_bytes(shape.group(1)), inst,
                " all-reduce-start(" in text))
        finished = DONE_OF.search(text)
        if finished:
            dones[finished.group(1)] = inst
        called = scopes.CALLS.search(text)
        if called and " fusion(" in text:
            callers[called.group(1)] = inst
    found = []
    for computation, entry in held.items():
        piece = callers.get(computation)
        for scope, nbytes, inst, started in entry["reduces"]:
            if piece is None and started:
                found.append(AllReduce(scope, nbytes, None, inst,
                                       dones.get(inst)))
            elif piece is None:
                found.append(AllReduce(scope, nbytes, inst, None, None))
            elif entry["half"] == "start":
                found.append(AllReduce(scope, nbytes, None, piece,
                                       PIECE.sub(r"\1done\2", piece)))
    return found


def core_time(devices: dict, found) -> dict:
    """``{scope: {"synchronous_ns", "pieces_ns", "in_flight_ns",
    "executions"}}`` over the devices' ``XLA Ops`` events, summed over the
    devices: the synchronous all-reduces' durations; the asynchronous
    ones' start and done pieces' durations and the spans from start piece
    to the end of the done piece (the k-th start of a name is paired with
    its k-th done), with how many such pairs ran."""
    synchronous = {r.name: r.scope for r in found if r.name}
    starts = {r.start: r for r in found if r.start}
    dones = {r.done for r in found if r.done}
    by_scope = {}

    def entry(scope):
        return by_scope.setdefault(scope, dict.fromkeys(
            ("synchronous_ns", "pieces_ns", "in_flight_ns", "executions"),
            0))

    for dev in devices.values():
        seen = {}
        for name, s, d in dev["ops"]:
            inst = scopes.instruction_of(name)
            if inst in synchronous:
                entry(synchronous[inst])["synchronous_ns"] += d
            elif inst in starts or inst in dones:
                seen.setdefault(inst, []).append((s, d))
        for start, r in starts.items():
            e = entry(r.scope)
            for (s0, d0), (s1, d1) in zip(seen.get(start, []),
                                          seen.get(r.done, [])):
                e["executions"] += 1
                e["in_flight_ns"] += s1 + d1 - s0
                e["pieces_ns"] += d0 + d1
    return by_scope


def table(run) -> dict:
    """``{"found", "by_scope", "steps", "device_window_ns"}`` of this run,
    made once: the step's all-reduces, and from the device trace their
    time on the core by scope, the step executions and the traced window,
    both summed over the devices (all empty or 0 with no device trace:
    the CPU rehearsal)."""
    def make():
        found = all_reduces(scopes.hlo_text(run))
        trace_dir = run.results.get("trace_dir")
        devices = tracing.load(trace_dir) if trace_dir else {}
        t = {"found": found, "by_scope": {}, "steps": 0,
             "device_window_ns": 0}
        if devices:
            w0, w1 = tracing.window_of(devices)
            t.update(by_scope=core_time(devices, found),
                     steps=sum(len(d["modules"]) for d in devices.values()),
                     device_window_ns=(w1 - w0) * len(devices))
        log(t)
        return t
    return scopes.made_once(run, "async_collectives", make)


def log(t: dict) -> None:
    """The whole table the four readers are cut from, into the log."""
    scopes_found = sorted({r.scope for r in t["found"]})
    for scope in scopes_found:
        mine = [r for r in t["found"] if r.scope == scope]
        hidden = [r for r in mine if r.start]
        line = (f"async_collectives: {scope}: {len(hidden)} of {len(mine)} "
                f"all-reduces asynchronous, {sum(r.nbytes for r in hidden)} "
                f"of {sum(r.nbytes for r in mine)} bytes")
        e = t["by_scope"].get(scope)
        if e and t["steps"]:
            per_step = 1e-6 / t["steps"]
            line += (f"; a step and device {e['synchronous_ns'] * per_step:.4f}"
                     f" ms in synchronous ones, "
                     f"{e['pieces_ns'] * per_step:.4f} ms in start and done "
                     f"pieces, {e['in_flight_ns'] * per_step:.4f} ms from "
                     f"start to done ({e['executions']} pairs in "
                     f"{t['steps']} steps, all devices)")
        result.log(line)
