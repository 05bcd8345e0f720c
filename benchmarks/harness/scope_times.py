"""Time under one ``hvd::`` scope, and in the Pallas kernels under it.

``scopes.py`` splits the step by phase and by ResNet's parts.  This module
answers two other questions for a step that names its parts with
``jax.named_scope`` (``docs/timeline.md``): how long the device spends in
operations traced **under a given scope**, forward, backward and recomputed
alike (``seconds_under``), and how long in the **kernels** under it: the
custom calls whose ``op_name`` holds a kernel's name as a step of its path
(``pl.pallas_call(..., name=...)`` puts it there), for a share of the peak
(``share_of_peak``).  An operation belongs to the scopes of its own
``op_name``, a fusion to those of its root or of the nearest instruction
inside that names a phase (``scopes.op_names``).  A ``while``, ``conditional``
or ``call`` is not counted: the operations it runs are.

Like ``scopes.py`` the arithmetic works on plain tuples, and a program that
writes no such scope (the parent of the PR that added it) reads ``None``.
"""

import re

from . import scopes
from . import trace as tracing

SPAN = "hvd::"
CONTAINERS = ("while", "conditional", "call")
OPCODE_OF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?[\]\})] "
                       r"([a-z][a-z0-9\-]*)\(")


def opcodes(hlo_text: str) -> dict:
    """``{instruction name: opcode}`` of the compiled text."""
    found = {}
    for line in hlo_text.split("\n"):
        m = OPCODE_OF.match(line)
        if m:
            found[m.group(1)] = m.group(2)
    return found


def reduce(devices: dict, names: dict, codes: dict, kernels) -> dict:
    """Seconds a device, averaged over ``devices`` (``trace.load``):
    ``op_s`` all operations, ``by_scope`` those under each ``hvd::`` scope,
    ``by_kernel`` the custom calls under each name of ``kernels``,
    ``by_instruction`` each instruction, and ``programs`` the executions of
    the program that ran longest (the step)."""
    by_scope, by_kernel, programs, by_inst = {}, {}, {}, {}
    total = 0
    for dev in devices.values():
        for name, _, d in dev["ops"]:
            inst = scopes.instruction_of(name)
            if codes.get(inst) in CONTAINERS:
                continue
            total += d
            by_inst[inst] = by_inst.get(inst, 0) + d
            steps = (names.get(inst) or "").split("/")
            for scope in {s for s in steps if s.startswith(SPAN)}:
                by_scope[scope] = by_scope.get(scope, 0) + d
            if codes.get(inst) == "custom-call":
                for kernel in kernels:
                    if kernel in steps:
                        by_kernel[kernel] = by_kernel.get(kernel, 0) + d
        for name, _, d in dev["modules"]:
            entry = programs.setdefault(re.sub(r"\(.*$", "", name), [0, 0])
            entry[0] += 1
            entry[1] += d
    ns = 1e-9 / len(devices)
    runs = max(programs.values(), key=lambda e: e[1])[0] if programs else 0
    return {"op_s": total * ns,
            "by_scope": {k: v * ns for k, v in by_scope.items()},
            "by_kernel": {k: v * ns for k, v in by_kernel.items()},
            "by_instruction": {k: v * ns for k, v in by_inst.items()},
            "programs": runs / len(devices)}


KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv",
           "hvd_gmm", "hvd_tgmm")


def table(run):
    """``reduce`` of this run's trace, made once; ``None`` without a
    device trace (the CPU rehearsal)."""
    def make():
        trace_dir = run.results.get("trace_dir")
        devices = tracing.load(trace_dir) if trace_dir else {}
        if not devices:
            return None
        text = scopes.hlo_text(run)
        names = scopes.op_names(text)
        t = reduce(devices, names, opcodes(text), KERNELS)
        for scope, seconds in sorted(t["by_scope"].items()):
            scopes.result.log(
                f"scope_times: under {scope} {seconds:.5f} s "
                f"{100 * seconds / t['op_s']:.2f} % of {t['op_s']:.4f} s")
        for kernel, seconds in sorted(t["by_kernel"].items()):
            scopes.result.log(
                f"scope_times: kernel {kernel} {seconds:.5f} s over "
                f"{t['programs']:.0f} steps")
        for inst, seconds in tracing.top(t["by_instruction"], 40):
            scopes.result.log(
                f"scope_times: {seconds:.5f} s {inst} "
                f"{(names.get(inst) or '-')[-110:]}")
        return t
    return scopes.made_once(run, "scope_times", make)


def share_under(run, scope: str):
    """``100 x`` time under ``scope`` over all operations' time."""
    t = table(run)
    if t is None or scope not in t["by_scope"]:
        return None
    return 100.0 * t["by_scope"][scope] / t["op_s"]


def share_of_peak(run, part: str, kernels):
    """``100 x`` the operations of ``part`` (``flops/<config>.py:
    train_flops_by_part``, a sample) x the samples the traced steps
    trained on a chip, over the time in ``kernels`` x the chip's bf16
    peak."""
    t = table(run)
    if t is None or run.peaks is None or run.flops is None:
        return None
    seconds = sum(t["by_kernel"].get(k, 0) for k in kernels)
    if not seconds:
        return None
    samples = t["programs"] * run.cell["traffic"]["images_per_chip"]
    operations = run.flops.train_flops_by_part(run.config)[part] * samples
    return 100.0 * operations / (seconds * run.peaks["bf16_flops_per_s"])
