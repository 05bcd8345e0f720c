"""Device trace: how often the forward scan kernel of Kimi Delta Attention
runs a (layer, sequence): the executions of the ``hvd_kda_fwd`` custom calls
in the traced window over the traced steps x the KDA layers x the sequences
a step.  2.0 where every KDA layer runs it a second time in the
recomputation of its ``jax.checkpoint``, only to write again the output and
the chunks' states that its backward pass reads; a layer that keeps those
two across the recomputation (``models/kimi_linear.py: KEPT_KDA``) runs it
once, so layers 1 and 5 of the cell's four read 1.5.  An engagement
counter: lower is better, and the time it spares shows in
``kda_scan_share.train`` and ``kda_chunk_us.train``.  Absent without a
device trace, and where the step runs no such kernel."""

from harness import scope_times, scopes
from harness import trace as tracing

KERNEL = "hvd_kda_fwd"


def executions(devices: dict, names: dict, codes: dict) -> float:
    """Events a device of the custom calls whose ``op_name`` holds
    ``KERNEL`` as a step of its path (``trace.load``'s ``devices``, the
    step's ``op_name`` and opcode by instruction)."""
    found = 0
    for dev in devices.values():
        for name, _, _ in dev["ops"]:
            inst = scopes.instruction_of(name)
            found += codes.get(inst) == "custom-call" \
                and KERNEL in (names.get(inst) or "").split("/")
    return found / len(devices) if devices else 0.0


def read(run):
    t = scope_times.table(run)
    if t is None or not t["programs"]:
        return None

    def make():
        text = scopes.hlo_text(run)
        return executions(tracing.load(run.results["trace_dir"]),
                          scopes.op_names(text), scope_times.opcodes(text))

    found = scopes.made_once(run, "kda_fwd_executions", make)
    if not found:
        return None
    layers = len(run.config["linear_attn_config"]["kda_layers"])
    sequences = run.cell["traffic"]["images_per_chip"]
    return found / (t["programs"] * layers * sequences)
