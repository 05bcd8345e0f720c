"""The step's device time as one partition: every operation under exactly
one part of the program, and what is left.

The program exports its parts (``horovod_tpu/scopes.py: exported_parts``:
the step wrapper's, each loaded model module's ``PARTS``, the explicit
collectives' ``hvd::<kind>``) and writes them as ``jax.named_scope``s, so
that each is a step of the ``op_name`` of every operation traced under it
(``docs/timeline.md``, "The device plane").  The rule (``part_of``): *an
operation's part is the innermost step of its ``op_name`` that is an
exported part*; an explicit collective's scope counts where no other part
is in the path.  This module gives every device operation that is not a
``while``, ``conditional`` or ``call`` (they span what they run) exactly one
owner, so that the parts' times are **self** times and sum to the
operations' time, by these rules, in this order (``Step.owner``; the table
keeps the seconds each placed):

``name``       the instruction's own ``op_name`` has a part (a fusion's is
               its root's).
``inside``     a fusion whose own ``op_name`` has none: the part nearest
               the root inside the fused computation (the rule
               ``scopes.op_names`` has for phases; the job's
               ``optax.apply_updates`` add ends an optimizer's fusion).
``consumer``   an instruction **without metadata** that starts or ends an
               asynchronous copy (``copy-start`` / ``copy-done``, and the
               sliced form ``slice-start`` / ``slice-done``): the first
               instruction in program order that reads the copied value,
               which is the one the compiler prefetches for.  Tuples,
               ``get-tuple-element``s, bitcasts and a loop's carried values
               are looked through.
``consumers``  any other instruction without metadata (``copy``, a fusion
               XLA built itself): its consumers' part where they agree,
``producer``   else its first operand's that has one,
``container``  else the part of the ``while``, ``conditional`` or ``call``
               whose computation holds it.

What still has no owner is ``unattributed``: "named, no part" (an
``op_name`` with no part in it: code of the caller that the package cannot
scope) or "no name".  Each rule is an approximation: a fusion is counted
whole for one part though XLA fuses across scopes, and a copy that feeds
two parts is the first one's.

Like ``scopes.py`` the arithmetic works on plain text and tuples
(``Step``, ``reduce``), so that it is checked on hand-built ones; ``table``
is the only part that knows a run.  With a program that exports no parts
(the parent of the PR that added them) every reader gives ``None``.
"""

import base64
import collections
import hashlib
import importlib
import re
import time

from . import result
from . import scope_times
from . import scopes
from . import trace as tracing

UNATTRIBUTED = "unattributed"
NAMED_NO_PART, NO_NAME = "named, no part", "no name"
CONTAINERS = scope_times.CONTAINERS
#: Looked through on the way to a value's consumers.
PLUMBING = ("bitcast", "get-tuple-element", "tuple")
#: What XLA's memory-space and layout assignment put in, where it carries
#: no source metadata; a sliced prefetch prints as ``slice-start`` or as an
#: ``async-start`` around the slice.
ASYNC_COPIES = ("copy-start", "copy-done", "slice-start", "slice-done",
                "async-start", "async-done")
COPIES = ASYNC_COPIES + ("copy",)
LINE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*?[\]\})]) "
                  r"([a-z][a-z0-9\-]*)\((.*)$")
CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations|true_computation|"
    r"false_computation)=(?:\{([^}]*)\}|(%?[\w.\-]+))")
OPERAND = re.compile(r"%([\w.\-]+)")
INDEX = re.compile(r"\bindex=(\d+)")
MEMORY_SPACE = re.compile(r"S\((\d+)\)")
KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]*)"')

Instruction = collections.namedtuple(
    "Instruction", "name computation shape opcode operands op_name called "
    "index root")


def exported():
    """``(parts, collective parts)`` as the loaded program exports them;
    ``None`` for a program that exports none."""
    try:
        program = importlib.import_module("horovod_tpu.scopes")
    except ImportError:
        return None
    return program.exported_parts()


def part_of(op_name, parts, collectives=()):
    """The part of one ``op_name`` by the rule, or ``None``."""
    steps = (op_name or "").split("/")
    for step in reversed(steps):
        if step in parts:
            return step
    for step in reversed(steps):
        if any(step == c or step.startswith(c + "::") for c in collectives):
            return step
    return None


def compiler_made(inst) -> bool:
    """No source metadata: no ``op_name``, or one that is no path the
    program traced (XLA's own ``gather`` expansion, a parameter's copy)."""
    return "/" not in (inst.op_name or "")


def _operands(rest: str):
    """The operands' names from what follows an opcode's ``(``."""
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch in "([{") - (ch in ")]}")
        if depth == 0:
            end = i
            break
    return tuple(OPERAND.findall(rest[:end])), rest[end + 1:]


class Step:
    """The compiled step's text as instructions with their uses, and the
    owner of each by the module's rules."""

    def __init__(self, hlo_text: str, parts, collectives=()):
        self.parts, self.collectives = tuple(parts), tuple(collectives)
        self.instructions, self.order, self.users = {}, {}, {}
        self.caller, self.reducers = {}, set()
        computation = None
        for line in hlo_text.split("\n"):
            m = LINE.match(line)
            if not m:
                opened = scopes.COMPUTATION.match(line)
                if opened:
                    computation = opened.group(1)
                continue
            root, name, shape, opcode, rest = m.groups()
            operands, attributes = _operands(rest)
            op_name = scopes.OP_NAME.search(attributes)
            index = INDEX.search(attributes) \
                if opcode == "get-tuple-element" else None
            called = {kind: [c.lstrip("%") for c in (many or one).split(", ")]
                      for kind, many, one in CALLED.findall(attributes)}
            inst = Instruction(
                name, computation, shape, opcode, operands,
                op_name.group(1) if op_name else None, called,
                int(index.group(1)) if index else None, bool(root))
            self.instructions[name] = inst
            self.order.setdefault(computation, []).append(name)
            for operand in operands:
                self.users.setdefault(operand, []).append(name)
            for kind, names in called.items():
                for c in names:
                    if kind == "to_apply":
                        self.reducers.add(c)
                    else:
                        self.caller[c] = name
        self._owner, self._open = {}, set()

    # -- the rule on names ----------------------------------------------------

    def named(self, name):
        """The part in the instruction's own ``op_name`` or, for a fusion
        without one there, nearest the root inside it; with the rule."""
        inst = self.instructions[name]
        own = part_of(inst.op_name, self.parts, self.collectives)
        if own or inst.opcode != "fusion":
            return own, "name"
        for inner in reversed(self.order.get(
                (inst.called.get("calls") or [None])[0], [])):
            found = part_of(self.instructions[inner].op_name, self.parts,
                            self.collectives)
            if found:
                return found, "inside"
        return None, None

    # -- def-use --------------------------------------------------------------

    def consumers(self, name, seen=None):
        """The instructions that read ``name``'s value, in program order,
        looking through plumbing and a loop's carried values."""
        seen = set() if seen is None else seen
        found = []
        for user in self.users.get(name, ()):
            if (name, user) in seen:
                continue
            seen.add((name, user))
            inst = self.instructions[user]
            if inst.opcode not in PLUMBING:
                found.append(user)
            elif inst.opcode == "tuple" and inst.root and self.instructions[
                    self.caller.get(inst.computation, user)
                    ].opcode == "while":
                # Carried to the next iteration: the body's parameter's
                # elements at the same positions.
                at = {i for i, o in enumerate(inst.operands) if o == name}
                for other in self.order[inst.computation]:
                    o = self.instructions[other]
                    if o.opcode == "get-tuple-element" and o.index in at \
                            and self.instructions[o.operands[0]].opcode \
                            == "parameter":
                        found += self.consumers(other, seen)
            else:
                found += self.consumers(user, seen)
        return found

    def value_of(self, name):
        """Where the value of ``name`` is read from: the ``-done`` half of
        an asynchronous pair for its ``-start`` half, else itself."""
        if not self.instructions[name].opcode.endswith("-start"):
            return name
        return next((u for u in self.users.get(name, ()) if
                     self.instructions[u].opcode.endswith("-done")), name)

    def first_part(self, names):
        for name in names:
            part = self.owner(name)[0]
            if part != UNATTRIBUTED:
                return part
        return None

    def owner(self, name):
        """``(part or "unattributed", rule)`` of one instruction."""
        if name in self._owner:
            return self._owner[name]
        if name in self._open:          # a cycle through a loop's carry
            return UNATTRIBUTED, NO_NAME
        self._open.add(name)
        try:
            found = self._owner[name] = self._place(name)
        finally:
            self._open.discard(name)
        return found

    def _place(self, name):
        inst = self.instructions.get(name)
        if inst is None:
            return UNATTRIBUTED, NO_NAME
        part, rule = self.named(name)
        if part:
            return part, rule
        if not compiler_made(inst):
            return UNATTRIBUTED, NAMED_NO_PART
        if inst.opcode.endswith(("-start", "-done")):
            # The pair is one copy: its value is what the done half gives.
            part = self.first_part(self.consumers(self.value_of(name)))
            if part:
                return part, "consumer"
        else:
            theirs = {self.owner(c)[0] for c in self.consumers(name)}
            theirs.discard(UNATTRIBUTED)
            if len(theirs) == 1:
                return theirs.pop(), "consumers"
        part = self.first_part(self.producers(name))
        if part:
            return part, "producer"
        container = self.caller.get(inst.computation)
        if container:
            part = part_of(self.instructions[container].op_name, self.parts,
                           self.collectives) or self.owner(container)[0]
            if part != UNATTRIBUTED:
                return part, "container"
        return UNATTRIBUTED, NO_NAME

    def producers(self, name, depth=8):
        """The operands of ``name``, plumbing and the halves of a
        compiler-made asynchronous copy looked through."""
        found = []
        for operand in self.instructions[name].operands:
            inst = self.instructions.get(operand)
            if inst is None or inst.opcode in ("parameter", "constant"):
                continue
            if depth and (inst.opcode in PLUMBING or (
                    inst.opcode in ASYNC_COPIES and compiler_made(inst))):
                found += self.producers(operand, depth - 1)
            else:
                found.append(operand)
        return found

    # -- what a copy moves ----------------------------------------------------

    def copy_line(self, name) -> str:
        """Shape, bytes, memory spaces and first consumer of one
        compiler-made copy."""
        inst = start = self.instructions[name]
        if inst.opcode.endswith("-done"):
            start = self.instructions[inst.operands[0]]
        if start.opcode.endswith("-start"):     # (destination, source, ..)
            shape, origin = (re.split(r",\s+(?=[a-z]+\d*\[)",
                                      start.shape.strip("()")) + [""])[:2]
        else:
            source = self.instructions.get((inst.operands or [None])[0])
            shape, origin = inst.shape, source.shape if source else ""
        space = lambda s: "S(%s)" % (MEMORY_SPACE.findall(s) or ["0"])[-1]
        fed = self.fed_by(name)
        return (f"{re.sub(r'{.*', '', shape)} "
                f"{scopes.array_bytes(shape)} bytes "
                f"{space(origin)} -> {space(shape)} feeds "
                + (f"{fed.name} ({fed.opcode}) "
                   f"{(fed.op_name or '-')[-90:]}" if fed else "-"))

    def fed_by(self, name, depth=6):
        """The first instruction with source metadata that reads the value
        of the compiler-made copy ``name``, further copies looked through."""
        for user in self.consumers(self.value_of(name)):
            inst = self.instructions[user]
            if not compiler_made(inst) or not depth:
                return inst
            return self.fed_by(user, depth - 1) or inst
        return None


def reduce(devices: dict, step: Step) -> dict:
    """Seconds a device, averaged over ``devices`` (``trace.load``):
    ``op_s`` all operations but containers, ``by_part`` each part's self
    time and ``unattributed``, ``by_rule`` what each rule placed,
    ``copies_by_part`` the compiler-made copies' share of a part,
    ``by_copy`` each such instruction, ``inside`` the time of each part's
    operations by opcode, ``left`` each unattributed instruction, ``gaps``
    the idle time inside programs by the part of the operation that ends
    the gap, ``programs`` the executions of the program that ran longest
    (the step)."""
    by_part, by_rule, copies_by_part, by_copy = {}, {}, {}, {}
    inside, left, gaps, programs = {}, {}, {}, {}
    total = copy_total = 0
    read = {}

    def reading(event_name):
        if event_name not in read:
            name = scopes.instruction_of(event_name)
            inst = step.instructions.get(name)
            part, rule = step.owner(name)
            copy = inst is not None and inst.opcode in COPIES \
                and compiler_made(inst)
            kind = inst.opcode if inst is not None else "?"
            if kind == "fusion":
                kind = "fusion " + re.sub(r"[.\d]+$", "", name)
            read[event_name] = (
                name, inst is not None and inst.opcode in CONTAINERS,
                part, rule, copy, kind)
        return read[event_name]

    def add(table, key, d):
        table[key] = table.get(key, 0) + d

    for dev in devices.values():
        ops = []
        for event_name, s, d in dev["ops"]:
            name, container, part, rule, copy, kind = reading(event_name)
            if container:
                continue
            ops.append((s, s + d, part))
            total += d
            add(by_part, part, d)
            add(by_rule, rule, d)
            add(inside, (part, kind), d)
            if part == UNATTRIBUTED:
                add(left, name, d)
            if copy:
                copy_total += d
                add(copies_by_part, part, d)
                add(by_copy, name, d)
        for name, _, d in dev["modules"]:
            entry = programs.setdefault(re.sub(r"\(.*$", "", name), [0, 0])
            entry[0] += 1
            entry[1] += d
        running = tracing.union(tracing.spans(dev["modules"]))
        ops.sort()
        at, j = None, 0
        for start, end, part in ops:
            if at is not None and start > at:
                while j < len(running) and running[j][1] <= at:
                    j += 1
                if j < len(running) and running[j][0] <= at \
                        and start <= running[j][1]:
                    add(gaps, part, start - at)
            at = end if at is None else max(at, end)
    ns = 1e-9 / max(len(devices), 1)
    seconds = lambda table: {k: v * ns for k, v in table.items()}
    runs = max(programs.values(), key=lambda e: e[1])[0] if programs else 0
    return {"op_s": total * ns, "copy_s": copy_total * ns,
            "by_part": seconds(by_part), "by_rule": seconds(by_rule),
            "copies_by_part": seconds(copies_by_part),
            "by_copy": seconds(by_copy), "inside": seconds(inside),
            "left": seconds(left), "gaps": seconds(gaps),
            "programs": runs / max(len(devices), 1)}


def without_locations(hlo_text: str):
    """``scopes.stripped`` of the text with every Pallas kernel's
    serialized body (MLIR bytecode, which holds the file names and line
    numbers of the call that traced it) replaced by the hash of its text
    without locations: two programs that differ in names and in where
    their source lines are give the same text.  ``None`` where the
    installed MLIR cannot read a body."""
    try:
        from jax._src.interpreters import mlir
        from jaxlib.mlir import ir
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True

        def kernel(m):
            with context:
                module = ir.Module.parse(base64.b64decode(m.group(1)))
                text = module.operation.get_asm(enable_debug_info=False)
            return '"body":"%s"' % hashlib.sha256(text.encode()).hexdigest()
        return KERNEL_BODY.sub(kernel, scopes.stripped(hlo_text))
    except Exception as e:              # a reader never fails the run
        result.log(f"parts: a kernel's body could not be read: {e!r}"[:300])
        return None


def renamed(text: str) -> str:
    """``text`` with every instruction and computation named by the order
    of its first appearance: XLA makes an instruction's name from its
    ``op_name`` (``%jvp_jit_remainder__`` or ``%jit_remainder_``), which is
    no instruction's change."""
    order = {}
    return OPERAND.sub(
        lambda m: order.setdefault(m.group(0), f"%n{len(order)}"), text)


def log_table(t: dict, step: Step) -> None:
    log = result.log
    total, steps = t["op_s"], max(t["programs"], 1)
    ms = lambda s: 1e3 * s / steps
    pct = lambda s: 100 * s / total if total else 0.0
    log(f"parts: {t['programs']:.0f} steps, operations' durations summed "
        f"(containers left out) {total:.5f} s; parts {list(step.parts)}")
    for part, s in tracing.top(t["by_part"], 40):
        log(f"parts: {part} {ms(s):.3f} ms a step {pct(s):.3f} %, of it in "
            f"compiler-made copies {ms(t['copies_by_part'].get(part, 0)):.3f}"
            f" ms")
    log(f"parts: the partition sums to "
        f"{pct(sum(t['by_part'].values())):.4f} % of the operations' time")
    for rule, s in tracing.top(t["by_rule"], 12):
        log(f"parts: placed by rule '{rule}' {s:.5f} s {pct(s):.3f} %")
    log(f"parts: compiler-made copies {ms(t['copy_s']):.3f} ms a step "
        f"{pct(t['copy_s']):.3f} %")
    for name, s in tracing.top(t["by_copy"], 15):
        log(f"parts: copy {name} {ms(s):.4f} ms a step, "
            f"{step.owner(name)[0]} by '{step.owner(name)[1]}': "
            f"{step.copy_line(name)}")
    fed = {}
    for name, s in t["by_copy"].items():
        reader = step.fed_by(name)
        key = re.sub(r"^jit\([^/]*/", "", (reader.op_name or reader.name)
                     if reader else "-")
        fed[key] = fed.get(key, 0) + s
    for key, s in tracing.top(fed, 12):
        log(f"parts: copies that feed {key[-150:]} {ms(s):.4f} ms a step")
    for part, whole in tracing.top(t["by_part"], 40):
        kinds = {k: s for (p, k), s in t["inside"].items() if p == part}
        for kind, s in tracing.top(kinds, 8) if pct(whole) >= 0.2 else ():
            log(f"parts: inside {part}, {kind} {ms(s):.4f} ms a step")
    for name, s in tracing.top(t["left"], 12):
        inst = step.instructions.get(name)
        log(f"parts: unattributed {name} {ms(s):.4f} ms a step, "
            f"{step.owner(name)[1]}: "
            f"{inst.opcode if inst else '?'} "
            f"{(inst.op_name or '-')[-100:] if inst else '-'}")
    for part, s in tracing.top(t["gaps"], 8):
        log(f"parts: idle inside a program before an operation of {part} "
            f"{s:.6f} s")


def table(run):
    """``reduce`` of this run's trace over the program's exported parts,
    made and logged once; ``None`` without a device trace (the CPU
    rehearsal) or where the program exports no parts."""
    def make():
        trace_dir, names = run.results.get("trace_dir"), exported()
        if not trace_dir or names is None:
            return None
        t0 = time.monotonic()
        devices = tracing.load(trace_dir)
        if not devices:
            return None
        loaded = time.monotonic() - t0
        text = scopes.hlo_text(run)     # made once a run, by whoever asks
        t0 = time.monotonic()
        step = Step(text, *names)
        t = reduce(devices, step)
        t["parts"] = step.parts
        log_table(t, step)
        bare = without_locations(text)
        if bare is not None:
            sha = lambda s: hashlib.sha256(s.encode()).hexdigest()
            result.log(
                "parts: the step's text without metadata and without the "
                f"kernels' source locations: sha256 {sha(bare)}; with its "
                "instructions named by their order of appearance: sha256 "
                + sha(renamed(bare)))
        result.log(f"parts: read in {time.monotonic() - t0:.1f} s after "
                   f"{loaded:.1f} s to load the trace "
                   f"({len(step.instructions)} instructions)")
        return t
    return scopes.made_once(run, "parts", make)


def share(run, part: str):
    """``100 x`` the self time of ``part`` over all operations' time;
    ``None`` where the program exports no such part."""
    t = table(run)
    if t is None or (part != UNATTRIBUTED and part not in t["parts"]):
        return None
    return 100.0 * t["by_part"].get(part, 0.0) / t["op_s"]


def copy_share(run):
    """``100 x`` the time in compiler-made copies over all operations'."""
    t = table(run)
    return None if t is None else 100.0 * t["copy_s"] / t["op_s"]
