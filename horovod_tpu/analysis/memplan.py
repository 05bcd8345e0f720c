"""hvdmem — static HBM liveness, donation, and budget analysis (HVD3xx).

Every subsystem in this repo ultimately fights over one resource: device
memory.  The paged KV cache (PR 4) exists because slot reservations
overshot it, quantized KV blocks (PR 7) exist because bf16 blocks filled
it, and the donated-then-consumed cache hazard (PR 4) was a *runtime*
crash whose shape is fully visible statically.  vLLM answers the same
questions dynamically (block accounting at admission) and XLA answers
them opaquely (buffer-donation aliasing at compile time); hvdmem makes
both **auditable before a program ever OOMs a chip**.

Two cooperating halves, mirroring hvdlint's AST/jaxpr split:

* **jaxpr liveness walk** (``measure_closed_jaxpr`` /
  ``measure_step_fn``): per-eqn live-set byte accounting — last-use
  analysis over eqn invars/outvars, sub-jaxprs recursed (``scan`` bodies
  carry-aware and counted ONCE, never multiplied by trip count; ``cond``
  branches max'd; single-eqn ``pjit``/``shard_map`` wrappers unwrapped so
  per-shard avals — already divided by the mesh axis sizes for the
  sharded dims — are what gets accounted) — producing a
  ``peak_live_bytes`` estimate plus a per-primitive allocation breakdown.
  Rules on top of the walk: HVD300 (donatable-but-undonated), HVD302
  (peak exceeds ``HVD_MEM_BUDGET_BYTES`` / probed HBM), HVD303
  (silent bf16→f32 upcast blowup), HVD304 (fusion bucket overshooting
  the tensor-fusion threshold knob).
* **AST rules** (``analyze_source`` / ``analyze_paths``, the CLI
  ``--mem`` pass): the source-level shapes of the same hazards — HVD300
  (a jit'ted local function that functionally updates a parameter via
  ``.at[...]`` and returns the update, with no ``donate_argnums`` at the
  jit site) and HVD301 (a variable passed into a donated argument slot
  and *read again* after the call — the PR 4 donated-then-consumed cache
  bug caught statically instead of at runtime via ``is_deleted``).
  Stdlib-only (ast), same pragma/suppression contract as hvdlint.

Surfacing matches the PR 2 collective census: ``HVD_ANALYZE=1`` runs the
walk on every first compile (analysis/hook.py), the result lands in
``core.analysis_reports()`` (``JaxprReport.memory``), in the active
timeline as ``MEMORY_CENSUS`` counter events, and in bench.py's JSON
record under ``memory_census``.  The serve engine folds its *actual*
allocation plan — ``paged_block_bytes() * num_blocks`` + weight bytes —
into the same budget check at construction and exposes the result as
``kv_headroom_bytes`` on ``healthz``/``/metrics`` (docs/serving.md).
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, \
    Set, Tuple

from .findings import Finding, rule_selected

# Bytes below which an undonated-but-donatable arg is noise, not a
# finding: donating a [B]-sized token vector saves nothing, donating a
# KV pool halves steady-state decode footprint.
DONATION_MIN_BYTES = 1 << 20

def upcast_min_bytes_default() -> int:
    """Floor for one bf16/f16 → f32 promotion to count toward HVD303
    (HVD_MEM_UPCAST_MIN_BYTES, bytes): the f32 layernorm islands the
    serve adapter runs on purpose are a few KB; a whole activation/param
    set silently widening is MBs.  Read per call like the sibling knobs
    so a malformed value degrades to the default instead of breaking the
    package import."""
    try:
        return int(os.environ.get("HVD_MEM_UPCAST_MIN_BYTES",
                                  str(8 << 20)))
    except ValueError:
        return 8 << 20


def fusion_threshold_bytes() -> int:
    """The tensor-fusion bucket bound (HOROVOD_FUSION_THRESHOLD, bytes —
    the same knob the eager fusion path sizes its flat buffers by)."""
    try:
        return int(os.environ.get("HOROVOD_FUSION_THRESHOLD",
                                  str(128 << 20)))
    except ValueError:
        return 128 << 20


def device_budget_bytes() -> Optional[int]:
    """The HBM budget the HVD302 check measures against:
    ``HVD_MEM_BUDGET_BYTES`` when set, else the probed per-device memory
    limit, else None (no budget known — HVD302 stays silent)."""
    env = os.environ.get("HVD_MEM_BUDGET_BYTES", "")
    if env:
        try:
            return int(env)
        except ValueError:
            return None
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            limit = int(stats.get("bytes_limit", 0))
            return limit or None
    except Exception:
        pass
    return None


def params_bytes(tree: Any) -> int:
    """Total bytes of a param/array pytree (0 for None/array-free)."""
    if tree is None:
        return 0
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        return 0
    total = 0
    for leaf in leaves:
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MemReport:
    """Result of one liveness walk (or one pool-budget check)."""

    label: str
    peak_live_bytes: int = 0
    input_bytes: int = 0
    output_bytes: int = 0
    # prim name -> {"count": eqn executions (scan bodies counted once),
    # "bytes": output bytes those eqns allocate}
    by_primitive: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    budget_bytes: Optional[int] = None
    headroom_bytes: Optional[int] = None
    upcast_f32_bytes: int = 0
    findings: List[Finding] = dataclasses.field(default_factory=list)

    #: Duck-type compatibility with JaxprReport consumers (bench.py reads
    #: ``reports[-1].census``): a MemReport carries no collective census.
    @property
    def census(self) -> dict:
        return {}

    @property
    def memory(self) -> dict:
        return self.to_dict()

    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "label": self.label,
            "peak_live_bytes": int(self.peak_live_bytes),
            "input_bytes": int(self.input_bytes),
            "output_bytes": int(self.output_bytes),
            "budget_bytes": self.budget_bytes,
            "headroom_bytes": self.headroom_bytes,
            "upcast_f32_bytes": int(self.upcast_f32_bytes),
            "by_primitive": {k: dict(v)
                             for k, v in sorted(self.by_primitive.items())},
        }


# ---------------------------------------------------------------------------
# Jaxpr liveness walk
# ---------------------------------------------------------------------------

def _aval_bytes(aval: Any) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    try:
        return int(size) * int(dtype.itemsize)
    except Exception:
        return 0


def sharding_divisor(sharding: Any) -> int:
    """How many ways a NamedSharding-style sharding splits an array:
    the product of the mesh axis sizes named by its spec ("divided by
    mesh axis sizes for the sharded dims").  1 for replicated/unknown."""
    try:
        spec = getattr(sharding, "spec", None)
        mesh = getattr(sharding, "mesh", None)
        if spec is None or mesh is None:
            return 1
        shape = dict(mesh.shape)
        div = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            for axis in axes:
                div *= int(shape.get(axis, 1))
        return max(div, 1)
    except Exception:
        return 1


class _LivenessWalker:
    """Simulates allocation order over a jaxpr: outputs of an eqn are
    allocated before its inputs can die (XLA cannot free an operand mid-
    op), values die after their last read unless pinned (non-donated
    top-level inputs: the caller still holds them, XLA cannot reuse the
    buffers), sub-programs contribute their internal transient (their
    peak beyond the boundary values the outer level already counts)."""

    def __init__(self, report: MemReport, fusion_threshold: int,
                 upcast_min: int):
        from jax.extend import core
        self._var = core.Var
        self.report = report
        self.fusion_threshold = fusion_threshold
        self.upcast_min = upcast_min
        self._upcast_sites = 0
        self._first_upcast = ""

    # -- helpers ------------------------------------------------------------

    def _as_jaxpr(self, obj):
        from .jaxpr_check import _as_jaxpr
        return _as_jaxpr(obj)

    def _sub_jaxprs(self, eqn) -> List[Any]:
        subs: List[Any] = []
        for val in eqn.params.values():
            for item in (val if isinstance(val, (tuple, list)) else (val,)):
                j = self._as_jaxpr(item)
                if j is not None:
                    subs.append(j)
        return subs

    def _boundary_bytes(self, j) -> int:
        return sum(_aval_bytes(v.aval)
                   for v in list(j.constvars) + list(j.invars))

    def _transient(self, sub) -> int:
        """A sub-program's peak beyond its boundary values (its invars /
        constvars alias outer operands already counted as live)."""
        j = self._as_jaxpr(sub)
        if j is None:
            return 0
        peak = self.walk(j, pinned=frozenset(), divisors={})
        return max(0, peak - self._boundary_bytes(j))

    def _eqn_transient(self, eqn) -> int:
        name = eqn.primitive.name
        if name == "cond":
            # Branches are exclusive at runtime: peak takes the MAX.
            return max((self._transient(b)
                        for b in eqn.params.get("branches", ())), default=0)
        if name == "scan":
            # Carry-aware: the body's working set exists once per
            # iteration, sequentially — its transient counts ONCE, never
            # multiplied by trip count (the stacked xs/ys already sit in
            # the outer eqn's operands/results).
            return self._transient(eqn.params.get("jaxpr"))
        if name in ("while", "while_loop"):
            return max(self._transient(eqn.params.get("cond_jaxpr")),
                       self._transient(eqn.params.get("body_jaxpr")))
        return max((self._transient(s) for s in self._sub_jaxprs(eqn)),
                   default=0)

    # -- per-eqn rule checks ------------------------------------------------

    def _check_upcast(self, eqn) -> None:
        """HVD303 input gathering: a bf16/f16 value promoted to f32/f64,
        element count preserved, past the size floor."""
        if eqn.primitive.name != "convert_element_type":
            return
        try:
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
        except (IndexError, AttributeError):
            return
        src_dt = str(getattr(src, "dtype", ""))
        dst_dt = str(getattr(dst, "dtype", ""))
        if src_dt not in ("bfloat16", "float16") or \
                dst_dt not in ("float32", "float64"):
            return
        out_bytes = _aval_bytes(dst)
        if out_bytes < self.upcast_min:
            return
        self.report.upcast_f32_bytes += out_bytes
        self._upcast_sites += 1
        if not self._first_upcast:
            self._first_upcast = (
                f"{src_dt}{tuple(getattr(src, 'shape', ()))} -> {dst_dt}")

    def _check_fusion(self, eqn) -> None:
        """HVD304: a rank-1 flat-buffer concatenation bigger than the
        tensor-fusion threshold knob — the fused-bucket overshoot that
        doubles a step's transient footprint past what the knob
        promises."""
        if eqn.primitive.name != "concatenate" or not eqn.outvars:
            return
        out = eqn.outvars[0].aval
        if len(getattr(out, "shape", (0, 0))) != 1:
            return
        out_bytes = _aval_bytes(out)
        if out_bytes > self.fusion_threshold:
            self.report.findings.append(Finding(
                rule="HVD304", path=self.report.label, line=0, col=0,
                source="mem",
                message=f"fused flat buffer of {out_bytes} bytes exceeds "
                        f"the tensor-fusion threshold "
                        f"({self.fusion_threshold} bytes, "
                        f"HOROVOD_FUSION_THRESHOLD) — the bucket overshoot "
                        f"costs its full size twice (gather-in + "
                        f"collective result) at peak"))

    def finish_upcast(self) -> None:
        """HVD303 fires when the promotions dominate: total upcast bytes
        at least a quarter of the peak ("promotes the whole live set"),
        not the few param-sized bf16→f32 accumulation casts every
        mixed-precision backward pass legitimately performs."""
        up = self.report.upcast_f32_bytes
        if self._upcast_sites and \
                up * 4 >= max(self.report.peak_live_bytes, 1):
            self.report.findings.append(Finding(
                rule="HVD303", path=self.report.label, line=0, col=0,
                source="mem",
                message=f"{self._upcast_sites} low-precision value(s) "
                        f"promoted to f32 for {up} bytes — "
                        f"{100 * up // max(self.report.peak_live_bytes, 1)}"
                        f"% of the {self.report.peak_live_bytes}-byte "
                        f"peak (first: {self._first_upcast}): the "
                        f"silent-upcast footprint — the live set widens "
                        f"2x through these ops"))

    # -- the walk -----------------------------------------------------------

    def walk(self, j, pinned, divisors: Dict[Any, int]) -> int:
        """Returns this jaxpr's peak live bytes, counting its boundary
        (constvars + invars) as live at entry.  ``pinned`` vars never die
        (non-donated top-level inputs); ``divisors`` divide specific
        invars' bytes (pjit shardings at the top level)."""
        j = self._as_jaxpr(j)
        if j is None:
            return 0

        def vbytes(v) -> int:
            return _aval_bytes(v.aval) // max(divisors.get(v, 1), 1)

        last_use: Dict[Any, int] = {}
        for i, eqn in enumerate(j.eqns):
            for v in eqn.invars:
                if isinstance(v, self._var):
                    last_use[v] = i
        outset = {v for v in j.outvars if isinstance(v, self._var)}
        live: Dict[Any, int] = {}
        live_bytes = 0
        for v in list(j.constvars) + list(j.invars):
            if v not in live:
                live[v] = vbytes(v)
                live_bytes += live[v]
        peak = live_bytes
        for i, eqn in enumerate(j.eqns):
            transient = self._eqn_transient(eqn)
            out_bytes = 0
            for v in eqn.outvars:
                b = vbytes(v)
                live_bytes += b - live.get(v, 0)
                live[v] = b
                out_bytes += b
            entry = self.report.by_primitive.setdefault(
                eqn.primitive.name, {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += out_bytes
            self._check_upcast(eqn)
            self._check_fusion(eqn)
            peak = max(peak, live_bytes + transient)
            for v in list(eqn.invars) + list(eqn.outvars):
                if not isinstance(v, self._var):
                    continue
                if v in outset or v in pinned:
                    continue
                if last_use.get(v, i) <= i:
                    live_bytes -= live.pop(v, 0)
        return peak


def _unwrap_wrappers(jaxpr, donated: Optional[Tuple[bool, ...]],
                     divisors: Dict[Any, int]):
    """Descend through single-eqn ``pjit``/``shard_map`` wrappers so the
    accounting sees the program the chip sees: a shard_map body's avals
    are PER-SHARD (bytes already divided by the mesh axis sizes for the
    sharded dims), and a pjit wrapper carries the donation flags
    (``donated_invars``) and shardings the caller compiled with.
    Explicitly passed donation wins over discovered flags."""
    while True:
        if jaxpr.constvars or len(jaxpr.eqns) != 1:
            return jaxpr, donated, divisors
        eqn = jaxpr.eqns[0]
        name = eqn.primitive.name
        if name not in ("jit", "shard_map"):
            return jaxpr, donated, divisors
        if list(eqn.invars) != list(jaxpr.invars) or \
                list(eqn.outvars) != list(jaxpr.outvars):
            return jaxpr, donated, divisors
        from .jaxpr_check import _as_jaxpr
        inner = _as_jaxpr(eqn.params.get("jaxpr"))
        if inner is None or len(inner.invars) != len(jaxpr.invars):
            return jaxpr, donated, divisors
        if name == "jit":
            if donated is None:
                flags = eqn.params.get("donated_invars")
                if flags is not None:
                    donated = tuple(bool(f) for f in flags)
            shardings = eqn.params.get("in_shardings") or ()
            divisors = {
                v: sharding_divisor(s)
                for v, s in zip(inner.invars, shardings)
                if sharding_divisor(s) > 1}
        else:  # shard_map: per-shard avals — nothing further to divide
            divisors = {}
        jaxpr = inner


def donated_invar_flags(args: Sequence[Any],
                        donate_argnums: Optional[Sequence[int]]
                        ) -> Optional[List[bool]]:
    """Expand per-ARGUMENT donation indices into per-INVAR (flattened
    pytree leaf) flags — ``jax.make_jaxpr`` flattens each argument into
    its leaves, so a donated pytree argument donates every one of its
    leaf invars, not just the leaf at its argument index."""
    if donate_argnums is None:
        return None
    import jax
    nums = set(int(i) for i in donate_argnums)
    flags: List[bool] = []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        flags.extend([i in nums] * n)
    return flags


def measure_closed_jaxpr(closed_jaxpr,
                         *,
                         label: str = "<jaxpr>",
                         donate_argnums: Optional[Sequence[int]] = None,
                         donated_invars: Optional[Sequence[bool]] = None,
                         budget_bytes: Optional[int] = None,
                         fusion_threshold: Optional[int] = None,
                         upcast_min_bytes: Optional[int] = None,
                         donation_min_bytes: int = DONATION_MIN_BYTES
                         ) -> MemReport:
    """Liveness-walk an already-traced program.

    Donation info comes from (highest precedence first)
    ``donated_invars`` (one bool per flattened invar — what
    ``donated_invar_flags`` computes from call args), ``donate_argnums``
    (positions into the INVAR list; only correct when every argument is
    a single leaf), or a top-level ``pjit`` wrapper's own
    ``donated_invars`` (``jax.make_jaxpr(jitted_fn)`` preserves them).
    With donation info available, HVD300 fires for each non-donated
    input that matches an output's shape+dtype (≥ ``donation_min_bytes``)
    — the args whose donation would let XLA alias the update in place.
    ``budget_bytes`` defaults to ``device_budget_bytes()``; when known,
    HVD302 fires if the peak estimate exceeds it.
    """
    report = MemReport(label=label)
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") \
        else closed_jaxpr
    donated: Optional[Tuple[bool, ...]] = None
    if donated_invars is not None:
        if len(donated_invars) == len(jaxpr.invars):
            donated = tuple(bool(f) for f in donated_invars)
        # Length mismatch (static/closed-over args): donation unknown —
        # stay conservative rather than mislabel leaves.
    elif donate_argnums is not None:
        nums = set(int(i) for i in donate_argnums)
        donated = tuple(i in nums for i in range(len(jaxpr.invars)))
    jaxpr, donated, divisors = _unwrap_wrappers(jaxpr, donated, divisors={})

    walker = _LivenessWalker(
        report,
        fusion_threshold if fusion_threshold is not None
        else fusion_threshold_bytes(),
        upcast_min_bytes if upcast_min_bytes is not None
        else upcast_min_bytes_default())

    def in_bytes(v) -> int:
        return _aval_bytes(v.aval) // max(divisors.get(v, 1), 1)

    # Top-level constvars (closure-captured weights under make_jaxpr) are
    # held by the caller exactly like non-donated invars: never freeable.
    if donated is None:
        pinned = frozenset(list(jaxpr.invars) + list(jaxpr.constvars))
    else:
        pinned = frozenset(
            [v for v, d in zip(jaxpr.invars, donated) if not d]
            + list(jaxpr.constvars))
    report.input_bytes = sum(in_bytes(v) for v in jaxpr.invars)
    report.output_bytes = sum(
        _aval_bytes(getattr(v, "aval", None)) for v in jaxpr.outvars)
    report.peak_live_bytes = walker.walk(jaxpr, pinned, divisors)
    walker.finish_upcast()

    # HVD300: donatable-but-undonated args (donation info required —
    # without it every input is conservatively pinned and no claim about
    # the caller's intent can be made).
    if donated is not None:
        out_avals = {}
        for v in jaxpr.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None:
                key = (tuple(getattr(aval, "shape", ())),
                       str(getattr(aval, "dtype", "?")))
                out_avals.setdefault(key, 0)
                out_avals[key] += 1
        outset = {v for v in jaxpr.outvars}
        # Already-donated invars consume their matching output first:
        # XLA aliases each donated buffer to one output, so that output
        # is spoken for and cannot justify donating a second arg.
        for v, d in zip(jaxpr.invars, donated):
            if not d:
                continue
            key = (tuple(getattr(v.aval, "shape", ())),
                   str(getattr(v.aval, "dtype", "?")))
            if out_avals.get(key):
                out_avals[key] -= 1
        for idx, (v, d) in enumerate(zip(jaxpr.invars, donated)):
            if d or v in outset:
                continue
            b = _aval_bytes(v.aval)
            if b < donation_min_bytes:
                continue
            key = (tuple(getattr(v.aval, "shape", ())),
                   str(getattr(v.aval, "dtype", "?")))
            if out_avals.get(key):
                out_avals[key] -= 1
                report.findings.append(Finding(
                    rule="HVD300", path=label, line=0, col=0, source="mem",
                    message=f"arg {idx} ({key[1]}{key[0]}, {b} bytes) "
                            f"matches an output's shape+dtype but is not "
                            f"donated — donating it lets XLA alias the "
                            f"update in place instead of holding both "
                            f"copies live"))

    budget = budget_bytes if budget_bytes is not None \
        else device_budget_bytes()
    report.budget_bytes = budget
    if budget is not None:
        report.headroom_bytes = int(budget) - int(report.peak_live_bytes)
        if report.headroom_bytes < 0:
            report.findings.append(Finding(
                rule="HVD302", path=label, line=0, col=0, source="mem",
                message=f"estimated peak live footprint "
                        f"{report.peak_live_bytes} bytes exceeds the "
                        f"memory budget {budget} bytes "
                        f"(HVD_MEM_BUDGET_BYTES / probed HBM) by "
                        f"{-report.headroom_bytes} bytes"))
    return report


def measure_step_fn(fn: Callable, args: Sequence[Any] = (),
                    kwargs: Optional[dict] = None, *,
                    label: Optional[str] = None,
                    donate_argnums: Optional[Sequence[int]] = None,
                    axis_env: Optional[Sequence[Tuple[str, int]]] = None,
                    **measure_kwargs) -> MemReport:
    """Trace ``fn(*args, **kwargs)`` and liveness-walk it.  Never raises
    on the user's program: a trace failure comes back as an HVD100-style
    empty report (the jaxpr checker owns trace-failure reporting)."""
    import jax
    name = label or getattr(fn, "__name__", None) or "step"
    kw = kwargs or {}
    try:
        traced = jax.make_jaxpr(
            lambda *a: fn(*a, **kw),
            axis_env=[tuple(e) for e in axis_env] if axis_env else None,
        )(*args)
    except Exception:
        return MemReport(label=name)
    return measure_closed_jaxpr(
        traced, label=name,
        donated_invars=donated_invar_flags(args, donate_argnums),
        **measure_kwargs)


# ---------------------------------------------------------------------------
# Pool-budget check (the serve engine's construction-time HVD302)
# ---------------------------------------------------------------------------

def check_pool_budget(label: str, pool_bytes: int, weight_bytes: int,
                      budget: Optional[int] = None) -> MemReport:
    """Verify a concrete allocation plan — the BlockManager pool
    (``paged_block_bytes() * num_blocks``) plus the replica's weight
    bytes — against the budget.  Returns a MemReport whose
    ``headroom_bytes`` is what the engine exposes as
    ``kv_headroom_bytes``; an HVD302 finding when the plan overshoots."""
    budget = budget if budget is not None else device_budget_bytes()
    report = MemReport(label=label,
                       peak_live_bytes=int(pool_bytes) + int(weight_bytes),
                       input_bytes=int(weight_bytes),
                       output_bytes=int(pool_bytes),
                       budget_bytes=budget)
    if budget is not None:
        report.headroom_bytes = int(budget) - report.peak_live_bytes
        if report.headroom_bytes < 0:
            report.findings.append(Finding(
                rule="HVD302", path=label, line=0, col=0, source="mem",
                message=f"KV pool ({pool_bytes} bytes) + weights "
                        f"({weight_bytes} bytes) = "
                        f"{report.peak_live_bytes} bytes exceeds the "
                        f"memory budget {budget} bytes by "
                        f"{-report.headroom_bytes} bytes — shrink "
                        f"HVD_SERVE_NUM_BLOCKS or quantize KV blocks "
                        f"(HVD_SERVE_KV_DTYPE=int8)"))
    return report


def publish_report(report: MemReport) -> None:
    """Log findings, append to ``core.analysis_reports()``, and chart
    the memory census on the active timeline — the exact surfacing the
    PR 2 collective census uses.  Never raises."""
    from ..utils import get_logger
    log = get_logger()
    for f in report.findings:
        log.warning("hvdmem: %s", f.format())
    try:
        from .. import core as _core
        _core._state.analysis_reports.append(report)
        tl = _core._state.timeline
        if tl is not None:
            tl.memory_census(report.label, report.to_dict())
    except Exception as e:  # pragma: no cover - publication is best-effort
        log.warning("hvdmem: could not publish report: %s", e)


# ---------------------------------------------------------------------------
# AST half (the CLI --mem pass): HVD300 / HVD301 source shapes
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jit", "pjit"}


def _is_jit_call(node: ast.AST) -> bool:
    """``jax.jit(...)`` / ``jax.pjit(...)`` / bare ``jit(...)``."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr in _JIT_NAMES
    if isinstance(f, ast.Name):
        return f.id in _JIT_NAMES
    return False


def _donated_indices(call: ast.Call) -> Optional[Set[int]]:
    """Literal ``donate_argnums`` of a jit call: a set of ints, empty set
    for an explicit ``()``, or None when absent / non-literal (the author
    either did not think about donation — HVD300's cue — or computed it
    dynamically, which the linter cannot follow)."""
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        val = kw.value
        if isinstance(val, ast.Constant) and isinstance(val.value, int):
            return {val.value}
        if isinstance(val, (ast.Tuple, ast.List)):
            out: Set[int] = set()
            for elt in val.elts:
                if isinstance(elt, ast.Constant) and \
                        isinstance(elt.value, int):
                    out.add(elt.value)
                else:
                    return set()  # partially dynamic: donation intended
            return out
        return set()  # non-literal donate_argnums: donation intended
    return None


def _target_key(node: ast.AST) -> Optional[Tuple[str, str]]:
    """Stable key for a Name or a ``self.attr`` attribute (the two
    binding shapes the dataflow tracks)."""
    if isinstance(node, ast.Name):
        return ("n", node.id)
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name):
        return ("a", f"{node.value.id}.{node.attr}")
    return None


def _root_name(node: ast.AST) -> Optional[str]:
    """Root Name of a Subscript/Attribute/Call chain (``cache["k"].at``
    → ``cache``; ``dict(cache)`` → first tainted arg's root)."""
    while True:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        elif isinstance(node, ast.Call):
            if node.args:
                node = node.args[0]
            else:
                return None
        else:
            return None


def _fn_updates_and_returns_param(fn: ast.AST) -> Optional[int]:
    """Does this function functionally update (``.at[...].set/add/...``)
    a value rooted at one of its parameters and return the update?
    Returns the offending line (the first ``.at`` use) or None.

    A ``lax.scan`` body threading its carry is NOT flagged: the carry is
    the *body's* parameter, not the jitted function's — taint is scoped
    per function."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
        return None
    args = fn.args
    params = {a.arg for a in list(args.args) + list(args.kwonlyargs)
              + list(args.posonlyargs)}
    tainted = set(params)
    updated: Set[str] = set()
    update_line: Optional[int] = None
    body = fn.body if isinstance(fn.body, list) else [ast.Return(fn.body)]

    def expr_is_update(node: ast.AST) -> bool:
        """``<tainted>...at[...].<set|add|...>(...)`` chain."""
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            return False
        sub = node.func.value
        if not isinstance(sub, ast.Subscript):
            return False
        at = sub.value
        if not (isinstance(at, ast.Attribute) and at.attr == "at"):
            return False
        root = _root_name(at.value)
        return root in tainted

    # Nested function defs own their parameters' taint — skip their
    # bodies (a scan/cond body updating ITS carry is the clean idiom).
    def _walk_skip_nested(root: ast.AST):
        stack = [root]
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                stack.append(child)

    nodes: List[ast.AST] = []
    for stmt in body:
        nodes.extend(_walk_skip_nested(stmt))
    nodes.sort(key=lambda n: (getattr(n, "lineno", 0),
                              getattr(n, "col_offset", 0)))

    for node in nodes:
        if isinstance(node, ast.Assign):
            val_update = expr_is_update(node.value)
            root = _root_name(node.value)
            for t in node.targets:
                names = [t] if isinstance(t, ast.Name) else \
                    [e for e in getattr(t, "elts", [])
                     if isinstance(e, ast.Name)]
                for n in names:
                    if val_update:
                        updated.add(n.id)
                        tainted.add(n.id)
                    elif root in tainted:
                        tainted.add(n.id)
                # ``pool["k"] = pool["k"].at[...].set(...)``: subscript/
                # attribute store into a tainted container.
                if not isinstance(t, ast.Name):
                    troot = _root_name(t)
                    if val_update and troot in tainted:
                        updated.add(troot)
            if val_update and update_line is None:
                update_line = node.lineno
        elif isinstance(node, ast.Return) and node.value is not None:
            for sub in ast.walk(node.value):
                if expr_is_update(sub):
                    return getattr(sub, "lineno", node.lineno)
                if isinstance(sub, ast.Name) and \
                        isinstance(sub.ctx, ast.Load) and \
                        sub.id in updated:
                    return update_line or getattr(node, "lineno",
                                                  fn.lineno)
    # Lambda: body already handled via synthetic Return above.
    return None


class _MemVisitor(ast.NodeVisitor):
    """Module walk collecting HVD300/HVD301 source findings."""

    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []
        self.fndefs: Dict[str, ast.AST] = {}

    def run(self, tree: ast.Module) -> List[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.fndefs.setdefault(node.name, node)
        # Attribute-bound donated callables are tracked MODULE-wide
        # (``self._fn = jax.jit(step, donate_argnums=...)`` in __init__,
        # called from another method — the engine's copy_block shape);
        # Name bindings stay function-scoped.
        attr_donated: Dict[Tuple[str, str], Set[int]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and _is_jit_call(node.value):
                idxs = _donated_indices(node.value)
                if not idxs:
                    continue
                for t in node.targets:
                    key = _target_key(t)
                    if key is not None and key[0] == "a":
                        attr_donated[key] = idxs
        for node in ast.walk(tree):
            if _is_jit_call(node):
                self._check_hvd300(node)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_hvd301(node, attr_donated)
        # A call inside a nested def is walked both from the outer and
        # the inner FunctionDef — dedupe by site.
        seen: Set[Tuple[str, int, int, str]] = set()
        uniq: List[Finding] = []
        for f in self.findings:
            key = (f.rule, f.line, f.col, f.message)
            if key not in seen:
                seen.add(key)
                uniq.append(f)
        return uniq

    # -- HVD300: donatable-but-undonated ------------------------------------

    def _check_hvd300(self, call: ast.Call) -> None:
        if _donated_indices(call) is not None:
            return  # donation considered at this jit site
        if not call.args:
            return
        target = call.args[0]
        fn = None
        if isinstance(target, ast.Lambda):
            fn = target
        elif isinstance(target, ast.Name):
            fn = self.fndefs.get(target.id)
        if fn is None:
            return
        line = _fn_updates_and_returns_param(fn)
        if line is None:
            return
        fname = getattr(fn, "name", "<lambda>")
        self.findings.append(Finding(
            rule="HVD300", path=self.path, line=call.lineno,
            col=call.col_offset + 1, source="mem",
            message=f"jit of '{fname}' has no donate_argnums but the "
                    f"function functionally updates a parameter "
                    f"(.at[...] at line {line}) and returns the update — "
                    f"without donation XLA holds both the old and new "
                    f"buffer live"))

    # -- HVD301: donated-then-used ------------------------------------------

    def _check_hvd301(self, fn: ast.AST,
                      attr_donated: Optional[Dict[Tuple[str, str],
                                                  Set[int]]] = None
                      ) -> None:
        donated_callables: Dict[Tuple[str, str], Set[int]] = \
            dict(attr_donated or {})
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if not _is_jit_call(node.value):
                continue
            idxs = _donated_indices(node.value)
            if not idxs:
                continue
            for t in node.targets:
                key = _target_key(t)
                if key is not None:
                    donated_callables[key] = idxs

        assigns = [n for n in ast.walk(fn) if isinstance(n, ast.Assign)]
        loads_by_key: Dict[Tuple[str, str], List[ast.AST]] = {}
        stores_by_key: Dict[Tuple[str, str], List[int]] = {}
        for node in ast.walk(fn):
            ctx = getattr(node, "ctx", None)
            key = _target_key(node)
            if key is None:
                continue
            if isinstance(ctx, ast.Load):
                # An Attribute load that is itself the base of a tracked
                # self.attr key shows as both Name load 'self' and the
                # Attribute — only the composite key matters here.
                loads_by_key.setdefault(key, []).append(node)
            elif isinstance(ctx, (ast.Store, ast.Del)):
                stores_by_key.setdefault(key, []).append(node.lineno)

        for call in ast.walk(fn):
            if not isinstance(call, ast.Call):
                continue
            idxs: Optional[Set[int]] = None
            fkey = _target_key(call.func)
            if fkey is not None and fkey in donated_callables:
                idxs = donated_callables[fkey]
            elif _is_jit_call(call.func):
                idxs = _donated_indices(call.func) or None
            if not idxs:
                continue
            enclosing = next(
                (a for a in assigns
                 if any(n is call for n in ast.walk(a.value))), None)
            rebound: Set[Tuple[str, str]] = set()
            if enclosing is not None:
                for t in enclosing.targets:
                    for n in ([t] + list(getattr(t, "elts", []))):
                        k = _target_key(n)
                        if k is not None:
                            rebound.add(k)
            for i in sorted(idxs):
                if i >= len(call.args):
                    continue
                akey = _target_key(call.args[i])
                if akey is None or akey in rebound:
                    continue
                later_stores = [ln for ln in stores_by_key.get(akey, [])
                                if ln > call.lineno]
                horizon = min(later_stores) if later_stores else None
                for use in loads_by_key.get(akey, []):
                    if use.lineno <= call.lineno:
                        continue
                    if horizon is not None and use.lineno >= horizon:
                        continue
                    label = akey[1]
                    self.findings.append(Finding(
                        rule="HVD301", path=self.path, line=use.lineno,
                        col=use.col_offset + 1, source="mem",
                        message=f"'{label}' was donated to the jitted "
                                f"call at line {call.lineno} "
                                f"(donate_argnums position {i}) and is "
                                f"read again here — the buffer is "
                                f"deleted after the call and this read "
                                f"raises at runtime (the PR 4 "
                                f"donated-then-consumed cache hazard)"))
                    break  # one finding per donated arg per call


def analyze_source(source: str, path: str = "<string>",
                   select: Sequence[str] = (),
                   ignore: Sequence[str] = ()) -> List[Finding]:
    """AST --mem pass over one source string (HVD300/HVD301), honoring
    the shared hvdlint pragma + select/ignore contract."""
    from .linter import _parse_pragmas, _suppressed
    try:
        tree = ast.parse(source, filename=path)
    except (SyntaxError, ValueError, RecursionError) as e:
        if not rule_selected("HVD000", select, ignore):
            return []
        line = getattr(e, "lineno", 0) or 0
        col = (getattr(e, "offset", 0) or 0)
        return [Finding(rule="HVD000", path=path, line=line,
                        col=max(col, 1), source="mem",
                        message=f"could not parse: {type(e).__name__}: "
                                f"{e}")]
    findings = _MemVisitor(path).run(tree)
    per_line, file_wide = _parse_pragmas(source)
    out: List[Finding] = []
    for f in findings:
        if not rule_selected(f.rule, select, ignore):
            continue
        f.suppressed = _suppressed(f, per_line, file_wide)
        out.append(f)
    return out


def analyze_paths(paths: Iterable[str], select: Sequence[str] = (),
                  ignore: Sequence[str] = ()) -> List[Finding]:
    """AST --mem pass over files/directories (the dogfooding command:
    ``python -m horovod_tpu.analysis --mem horovod_tpu examples``)."""
    from .linter import iter_python_files
    findings: List[Finding] = []
    files: List[str] = []
    for path in paths:
        if not os.path.exists(path):
            if rule_selected("HVD000", select, ignore):
                findings.append(Finding(
                    rule="HVD000", path=path, line=0, col=1, source="mem",
                    message="path does not exist"))
        else:
            files.append(path)
    for fpath in iter_python_files(files):
        try:
            with open(fpath, "rb") as fh:
                source = fh.read().decode("utf-8", errors="replace")
        except OSError as e:
            if rule_selected("HVD000", select, ignore):
                findings.append(Finding(
                    rule="HVD000", path=fpath, line=0, col=1, source="mem",
                    message=f"could not read file: {e}"))
            continue
        findings.extend(analyze_source(source, path=fpath, select=select,
                                       ignore=ignore))
    return findings
