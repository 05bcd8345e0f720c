"""Trace-level collective-consistency checker + collective census.

The reference framework discovers mismatched collective sequences at
RUNTIME: every rank submits requests, the coordinator's negotiation
phase diffs them, and the job is already wedged when the "Mismatched
allreduce" stall warning prints (controller.cc ComputeResponseList).  On
TPU the whole step program is visible as a jaxpr BEFORE compilation, so
the same contract is checkable statically: walk the (closed) jaxpr —
including ``cond``/``scan``/``while``/``pjit``/``shard_map`` sub-jaxprs —
and

* verify every collective primitive names an axis declared by an
  enclosing mesh/``shard_map`` (HVD101);
* flag ``lax.cond`` branches whose collective *signatures* (ordered
  primitive / axis / shape / dtype sequence) differ — the static
  analogue of the negotiation mismatch (HVD102);
* build a per-step **collective census**: count + estimated payload
  bytes per primitive (``scan`` bodies multiply by trip count; ``while``
  bodies count once and are marked dynamic).  ``timeline.py`` renders
  the census as Chrome-trace counter events and ``bench.py`` attaches it
  to its JSON record under ``HVD_ANALYZE=1``.

A step function that fails to trace is reported as an HVD100 finding —
the checker never raises on user programs, so the ``HVD_ANALYZE=1``
trace-time hook can run mid-training without risk.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence, \
    Tuple

import jax

from .findings import Finding

# Axis-name collective primitives.
COLLECTIVE_PRIMS = {
    "psum", "pmin", "pmax", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pbroadcast",
    "pgather",
}


def collective_name(eqn) -> Optional[str]:
    """The census name of a collective eqn, None for any other eqn.
    Under shard_map's varying-axes tracking ``jax.lax.psum`` of a varying
    value traces as ``psum_invariant`` — the same all-reduce on the wire,
    so it is counted as ``psum``."""
    name = eqn.primitive.name
    if name == "psum_invariant":
        return "psum"
    return name if name in COLLECTIVE_PRIMS else None


@dataclasses.dataclass
class JaxprReport:
    """Result of one program check: findings + the collective census."""

    label: str
    findings: List[Finding] = dataclasses.field(default_factory=list)
    # prim name -> {"count": executions (scan-expanded), "bytes":
    # estimated payload-in bytes across those executions}
    census: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    dynamic_loops: int = 0   # while-loops whose trip count is unknown
    # hvdmem liveness walk of the same program (memplan.MemReport
    # .to_dict(); attached by the HVD_ANALYZE hook): peak_live_bytes,
    # per-primitive allocation breakdown, budget headroom.
    memory: Optional[dict] = None
    # hvdshard sharding/communication walk of the same program
    # (shardplan.CommReport.to_dict(); attached by the HVD_ANALYZE
    # hook): wire bytes, ICI/DCN split, reshard events, budgets.
    comm: Optional[dict] = None

    def ok(self) -> bool:
        return not self.findings

    def total_collectives(self) -> int:
        return sum(c["count"] for c in self.census.values())

    def total_bytes(self) -> int:
        return sum(c["bytes"] for c in self.census.values())

    def to_dict(self) -> dict:
        return {"label": self.label,
                "findings": [f.to_dict() for f in self.findings],
                "census": self.census,
                "dynamic_loops": self.dynamic_loops,
                "memory": self.memory,
                "comm": self.comm}


# -- jaxpr plumbing ---------------------------------------------------------

def _as_jaxpr(obj: Any):
    """Unwrap ClosedJaxpr → Jaxpr; pass Jaxpr through; None otherwise."""
    from jax.extend import core
    if isinstance(obj, core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, core.Jaxpr):
        return obj
    return None


def _sub_jaxprs(eqn) -> List[Any]:
    """Every jaxpr hiding in an eqn's params (generic: covers pjit,
    custom_jvp/vjp, remat, closed_call, future primitives)."""
    subs: List[Any] = []
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            j = _as_jaxpr(item)
            if j is not None:
                subs.append(j)
    return subs


def _axis_names(params: dict) -> Tuple[str, ...]:
    """String axis names a collective eqn references (ints from vmap's
    positional axes are not mesh axes and are skipped)."""
    raw = params.get("axes", params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        size = getattr(aval, "size", None)
        dtype = getattr(aval, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(dtype.itemsize)
    return total


def _signature(jaxpr) -> Tuple:
    """Ordered collective signature of a (sub)program: the tuple the
    reference's negotiation would have diffed across ranks.  ``scan``
    bodies are expanded by trip count — a psum scanned 2× and one scanned
    5× are DIFFERENT collective sequences at runtime; ``while`` bodies
    (unknown trips) contribute their body signature once."""
    sig: List[Tuple] = []

    def rec(j) -> None:
        for eqn in j.eqns:
            coll = collective_name(eqn)
            if coll is not None:
                shapes = tuple(
                    (tuple(getattr(v.aval, "shape", ())),
                     str(getattr(v.aval, "dtype", "?")))
                    for v in eqn.invars if getattr(v, "aval", None)
                    is not None)
                sig.append((coll, _axis_names(eqn.params), shapes))
            elif eqn.primitive.name == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                sig.extend(_signature(eqn.params.get("jaxpr")) * length)
            else:
                for sub in _sub_jaxprs(eqn):
                    rec(sub)

    j = _as_jaxpr(jaxpr)
    if j is not None:
        rec(j)
    return tuple(sig)


def _fmt_sig(sig: Tuple) -> str:
    if not sig:
        return "(no collectives)"
    return "; ".join(
        f"{name}[{','.join(axes) or '-'}]"
        f"({'+'.join(f'{s}{d}' for s, d in shapes) or '-'})"
        for name, axes, shapes in sig)


# -- the walker -------------------------------------------------------------

class _Walker:
    def __init__(self, report: JaxprReport):
        self.report = report

    def emit(self, rule: str, message: str) -> None:
        self.report.findings.append(Finding(
            rule=rule, path=self.report.label, line=0, col=0,
            message=message, source="jaxpr"))

    def record(self, eqn, mult: int) -> None:
        name = collective_name(eqn)
        entry = self.report.census.setdefault(
            name, {"count": 0, "bytes": 0})
        entry["count"] += mult
        entry["bytes"] += mult * _payload_bytes(eqn)

    def walk(self, jaxpr, declared: Optional[FrozenSet[str]],
             mult: int) -> None:
        j = _as_jaxpr(jaxpr)
        if j is None:
            return
        for eqn in j.eqns:
            coll = collective_name(eqn)
            if coll is not None:
                self.record(eqn, mult)
                if declared is not None:
                    for axis in _axis_names(eqn.params):
                        if axis not in declared:
                            self.emit(
                                "HVD101",
                                f"collective '{coll}' reduces over axis "
                                f"'{axis}' but the enclosing mesh only "
                                f"declares {sorted(declared)}")
                continue
            name = eqn.primitive.name
            if name == "cond":
                self._walk_cond(eqn, declared, mult)
            elif name == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                self.walk(eqn.params.get("jaxpr"), declared, mult * length)
            elif name in ("while", "while_loop"):
                self.report.dynamic_loops += 1
                self.walk(eqn.params.get("cond_jaxpr"), declared, mult)
                self.walk(eqn.params.get("body_jaxpr"), declared, mult)
            elif name == "shard_map":
                mesh = eqn.params.get("mesh")
                axes = tuple(getattr(mesh, "axis_names", ()) or ())
                inner = (declared or frozenset()) | frozenset(
                    a for a in axes if isinstance(a, str))
                self.walk(eqn.params.get("jaxpr"), inner or None, mult)
            else:
                for sub in _sub_jaxprs(eqn):
                    self.walk(sub, declared, mult)

    def _walk_cond(self, eqn, declared, mult: int) -> None:
        branches = eqn.params.get("branches", ())
        sigs = [_signature(b) for b in branches]
        if sigs and any(s != sigs[0] for s in sigs[1:]):
            rendered = "; vs ".join(
                f"branch {i}: {_fmt_sig(s)}" for i, s in enumerate(sigs))
            self.emit(
                "HVD102",
                f"lax.cond branches disagree on their collective "
                f"signature — {rendered}.  If the predicate diverges "
                f"across ranks this deadlocks exactly like a Horovod "
                f"negotiation mismatch")
        # Census counts every branch's collectives (static upper bound:
        # which branch runs is a runtime property).
        for b in branches:
            self.walk(b, declared, mult)


# -- public API -------------------------------------------------------------

def check_closed_jaxpr(closed_jaxpr,
                       declared_axes: Optional[Sequence[str]] = None,
                       label: str = "<jaxpr>") -> JaxprReport:
    """Check an already-traced program.  ``declared_axes=None`` means "no
    declaration info at this level" — axis checking then activates only
    inside ``shard_map`` regions, whose mesh declares its own axes."""
    report = JaxprReport(label=label)
    declared = frozenset(declared_axes) if declared_axes is not None \
        else None
    _Walker(report).walk(closed_jaxpr, declared, 1)
    return report


def check_step_fn(fn: Callable,
                  args: Sequence[Any] = (),
                  kwargs: Optional[dict] = None,
                  *,
                  axis_env: Optional[Sequence[Tuple[str, int]]] = None,
                  declared_axes: Optional[Sequence[str]] = None,
                  label: Optional[str] = None) -> JaxprReport:
    """Trace ``fn(*args, **kwargs)`` with ``jax.make_jaxpr`` and check it.

    ``axis_env`` binds axis names for tracing un-shard_mapped per-slot
    functions (``[("hvd", 8)]``); a fully wrapped ``shard_map`` step needs
    neither.  ``declared_axes`` is the set the deployment actually
    provides — it defaults to the ``axis_env`` names, so pass it
    explicitly to detect a collective using an axis the mesh won't carry.

    Never raises on the user's program: trace failures come back as an
    HVD100 finding (unbound-axis NameErrors as HVD101), so the
    ``HVD_ANALYZE=1`` hook is safe mid-training.
    """
    name = label or getattr(fn, "__name__", None) or "step"
    kw = kwargs or {}
    try:
        traced = jax.make_jaxpr(
            lambda *a: fn(*a, **kw),
            axis_env=[tuple(e) for e in axis_env] if axis_env else None,
        )(*args)
    except Exception as e:  # loud-but-graceful: report, never crash
        report = JaxprReport(label=name)
        # jax raises NameError("unbound axis name: <axis>") for a
        # collective over an undeclared axis — only that literal message
        # shape is an HVD101.  Any other failure (including an ordinary
        # Python NameError from a typo'd variable, even one *named*
        # something like `axis_scale`) is a generic HVD100.
        if isinstance(e, NameError) and "unbound axis" in str(e).lower():
            report.findings.append(Finding(
                rule="HVD101", path=name, line=0, col=0, source="jaxpr",
                message=f"trace failed on an unbound axis name — a "
                        f"collective references an axis no enclosing "
                        f"mesh/shard_map declares: {e}"))
        else:
            report.findings.append(Finding(
                rule="HVD100", path=name, line=0, col=0, source="jaxpr",
                message=f"step function failed to trace: "
                        f"{type(e).__name__}: {e}"))
        return report
    declared: Optional[Sequence[str]] = declared_axes
    if declared is None and axis_env:
        declared = [a for a, _ in axis_env]
    report = check_closed_jaxpr(traced, declared_axes=declared, label=name)
    # Stash the traced program so downstream analyses (the hvdmem
    # liveness walk in analysis/hook.py) reuse this trace instead of
    # paying a second one; not part of to_dict().
    report._closed_jaxpr = traced
    return report
