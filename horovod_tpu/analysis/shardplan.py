"""hvdshard — static sharding & communication-plan analysis (HVD4xx).

The paper's core claim is that Horovod's *runtime* negotiation of
collective consistency becomes a *compile-time* property on XLA/SPMD.
PR 2 made the collectives a program explicitly issues statically
checkable (HVD1xx) and PR 10 did the same for HBM (HVD3xx) — but the
communication GSPMD inserts *silently* is still invisible until a step
is slow on the wrong fabric: a value produced under one sharding and
consumed under another becomes an implicit all-gather; a collective
whose axis spans hosts rides DCN at a fraction of ICI bandwidth.
hvdshard makes the whole communication plan auditable before compile:

Two cooperating halves, mirroring hvdmem's jaxpr/AST split:

* **jaxpr sharding walk** (``measure_closed_jaxpr_comm``): extracts
  per-value shardings from ``pjit``/``sharding_constraint``/``shard_map``
  equations and detects **implicit resharding** — produced under
  sharding A, consumed under sharding B, with estimated bytes moved
  (HVD400; an explicit ``with_sharding_constraint`` is the blessed way
  to reshard and is never flagged).  The same walk builds a
  **communication census**: per-collective payload bytes and wire bytes
  (payload × communicator group size; ``ppermute``/``pshuffle`` move
  their payload once per hop), with every mesh axis classified ICI vs
  DCN (``classify_mesh_axes``: an axis crosses DCN iff moving along it
  changes the device's ``process_index`` — the ``topology.py``
  cross/local split — overridable via ``HVD_COMM_DCN_AXES``).  Rules on
  top of the walk: HVD401 (per-step wire bytes exceed
  ``HVD_COMM_BUDGET_BYTES``; DCN wire bytes exceed the stricter
  ``HVD_COMM_DCN_BUDGET_BYTES`` sub-budget), HVD402 (a large replicated
  operand next to sharded peers that a known mesh axis would shard — the
  comm analogue of HVD300), HVD403 (a collective over an axis the mesh
  doesn't declare, or one flat collective mixing ICI and DCN axes —
  crossing process-set scopes at DCN speed for the whole payload;
  HVD102's negotiation-mismatch concern extended to multi-host process
  sets), HVD404 (a mesh axis of size > 1 that no collective and no
  sharding ever names — dead parallelism wasting chips).

* **AST rules** (``analyze_source`` / ``analyze_paths``, the CLI
  ``--comm`` pass): the source-level shapes — HVD400 (one variable
  annotated with two *different* literal ``PartitionSpec``s via
  ``with_sharding_constraint``/``device_put`` in the same function: GSPMD
  materializes both layouts, one of them via an implicit reshard;
  rebinding the constrained result to a new name is the deliberate-
  resharding idiom and stays clean) and HVD404 (a mesh built from
  literal axes whose sibling axes are exercised by literal specs in the
  same function while one axis never appears — flagged at the mesh
  construction).  Stdlib-only, same pragma/--select/--ignore contract.

Surfacing matches the PR 2/PR 10 censuses: ``HVD_ANALYZE=1`` rides this
walk on the SAME trace the collective and memory censuses use
(analysis/hook.py), the result lands on ``core.analysis_reports()``
(``JaxprReport.comm``), in the active timeline as ``COMM_CENSUS``
counter events, and in bench.py's JSON record under ``comm_census``.
The serve engine folds the comm budget into ``check_replica_plan()`` —
the static go/no-go combining hvdmem's HVD302 pool-vs-budget verdict
with HVD401, exposed on ``kv_stats``/``healthz`` (docs/serving.md): the
admission primitive a tensor/pipeline-sharded replica needs before it
is ever handed traffic.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import Finding, rule_selected

#: Reshardings below this are noise (a re-laid-out scalar counter), not
#: a finding; the KV-cache- and activation-sized implicit all-gathers
#: the rule exists for are MBs.  Parameterized per call for tests.
RESHARD_MIN_BYTES = 1 << 20

#: Floor for HVD402: a replicated bias vector next to a sharded batch is
#: the normal data-parallel layout; a replicated multi-MB operand whose
#: leading dim a declared axis divides evenly is a missed sharding.
REPLICATED_MIN_BYTES = 1 << 20


def comm_budget_bytes() -> Optional[int]:
    """Per-step wire-byte budget HVD401 measures against
    (``HVD_COMM_BUDGET_BYTES``); None (unset/malformed) disables the
    check.  Read per call like the sibling hvdmem knobs so a bad value
    degrades to "no budget" instead of breaking import."""
    try:
        env = os.environ.get("HVD_COMM_BUDGET_BYTES", "")
        return int(env) if env else None
    except ValueError:
        return None


def dcn_budget_bytes() -> Optional[int]:
    """The stricter DCN sub-budget (``HVD_COMM_DCN_BUDGET_BYTES``):
    bytes that cross hosts per step.  DCN bandwidth is an order of
    magnitude below ICI, so a plan can fit the total budget and still
    be DCN-bound — this knob catches that separately."""
    try:
        env = os.environ.get("HVD_COMM_DCN_BUDGET_BYTES", "")
        return int(env) if env else None
    except ValueError:
        return None


def dcn_axes_override() -> Tuple[str, ...]:
    """Mesh axes forced to DCN classification (``HVD_COMM_DCN_AXES``,
    comma-separated) — for single-process tests and for analyzing a
    program *for* a multi-host deployment from one host, where every
    local device shares one process_index."""
    raw = os.environ.get("HVD_COMM_DCN_AXES", "")
    return tuple(tok.strip() for tok in raw.split(",") if tok.strip())


def classify_mesh_axes(mesh: Any,
                       dcn_axes: Optional[Sequence[str]] = None
                       ) -> Dict[str, str]:
    """Map each mesh axis name → ``"ici"`` | ``"dcn"``.

    An axis is DCN iff moving along it (holding the other axes fixed)
    changes the device's ``process_index`` — the same host/process split
    ``topology.Topology`` reports as cross vs local, read off the mesh's
    actual device placement.  ``dcn_axes`` (default: the
    ``HVD_COMM_DCN_AXES`` override) forces listed axes to DCN regardless
    of placement.  Unknown/deviceless meshes classify everything ICI —
    the optimistic default matching a single-host run."""
    forced = set(dcn_axes if dcn_axes is not None else dcn_axes_override())
    out: Dict[str, str] = {}
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    devices = getattr(mesh, "devices", None)
    for i, name in enumerate(names):
        if not isinstance(name, str):
            continue
        kind = "ici"
        if name in forced:
            kind = "dcn"
        elif devices is not None:
            try:
                if devices.shape[i] > 1:
                    first = devices.take([0], axis=i)
                    for j in range(1, devices.shape[i]):
                        plane = devices.take([j], axis=i)
                        for a, b in zip(first.flat, plane.flat):
                            if a.process_index != b.process_index:
                                kind = "dcn"
                                break
                        if kind == "dcn":
                            break
            except Exception:
                pass
        out[name] = kind
    return out


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CommReport:
    """Result of one sharding/communication walk."""

    label: str
    # prim name -> {"count": executions (scan-expanded), "bytes": payload
    # bytes in, "wire_bytes": payload x group size, "dcn_bytes": the
    # wire bytes whose axes cross DCN}
    by_primitive: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    # axis name -> {"fabric": "ici"|"dcn", "size", "count",
    # "wire_bytes"}: per-axis attribution (a multi-axis collective's
    # wire bytes attribute to each axis it names — an upper bound per
    # axis, exact for single-axis collectives).
    by_axis: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    total_wire_bytes: int = 0
    dcn_wire_bytes: int = 0
    reshard_bytes: int = 0
    reshard_events: List[dict] = dataclasses.field(default_factory=list)
    axes_declared: Dict[str, int] = dataclasses.field(default_factory=dict)
    axes_used: Set[str] = dataclasses.field(default_factory=set)
    dynamic_loops: int = 0
    budget_bytes: Optional[int] = None
    dcn_budget_bytes: Optional[int] = None
    headroom_bytes: Optional[int] = None
    dcn_headroom_bytes: Optional[int] = None
    findings: List[Finding] = dataclasses.field(default_factory=list)

    #: Duck-type compatibility with JaxprReport consumers: a standalone
    #: CommReport carries no collective census and no memory walk.
    @property
    def census(self) -> dict:
        return {}

    @property
    def comm(self) -> dict:
        return self.to_dict()

    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "label": self.label,
            "total_wire_bytes": int(self.total_wire_bytes),
            "dcn_wire_bytes": int(self.dcn_wire_bytes),
            "reshard_bytes": int(self.reshard_bytes),
            "reshard_events": list(self.reshard_events),
            "budget_bytes": self.budget_bytes,
            "dcn_budget_bytes": self.dcn_budget_bytes,
            "headroom_bytes": self.headroom_bytes,
            "dcn_headroom_bytes": self.dcn_headroom_bytes,
            "dynamic_loops": int(self.dynamic_loops),
            "axes_declared": dict(sorted(self.axes_declared.items())),
            "axes_used": sorted(self.axes_used),
            "by_primitive": {k: dict(v)
                             for k, v in sorted(self.by_primitive.items())},
            "by_axis": {k: dict(v)
                        for k, v in sorted(self.by_axis.items())},
        }


# ---------------------------------------------------------------------------
# Jaxpr sharding walk
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


def _spec_key(sharding: Any, ndim: int) -> Optional[Tuple]:
    """Canonical per-dim sharding key of a NamedSharding-style sharding:
    a tuple (length ``ndim``, trailing replicated dims padded with None)
    of per-dim axis-name tuples.  None for UnspecifiedValue / spec-less
    shardings — "no claim", never compared."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    key: List[Optional[Tuple[str, ...]]] = []
    try:
        for entry in spec:
            if entry is None:
                key.append(None)
            elif isinstance(entry, (tuple, list)):
                key.append(tuple(entry))
            else:
                key.append((entry,))
    except TypeError:
        return None
    while len(key) < ndim:
        key.append(None)
    return tuple(key[:ndim])


def _key_axes(key: Optional[Tuple]) -> Set[str]:
    out: Set[str] = set()
    for entry in key or ():
        for axis in entry or ():
            if isinstance(axis, str):
                out.add(axis)
    return out


def _fmt_key(key: Optional[Tuple]) -> str:
    if key is None:
        return "<unspecified>"
    return "P(" + ", ".join(
        "None" if e is None else "+".join(e) for e in key) + ")"


def _axis_strings(obj: Any) -> List[str]:
    """Every axis-name string inside a nested names structure (shard_map
    ``in_names`` dicts ``{dim: (axes,)}``, spec tuples, plain strings)."""
    if isinstance(obj, str):
        return [obj]
    if isinstance(obj, dict):
        return [s for v in obj.values() for s in _axis_strings(v)]
    if isinstance(obj, (tuple, list)):
        return [s for v in obj for s in _axis_strings(v)]
    return []


class _CommWalker:
    """One pass over a (closed) jaxpr accumulating the communication
    census, per-value shardings, and HVD400/402/403 findings.  Mesh
    axes/fabrics accrete as the walk discovers meshes (shard_map params,
    NamedSharding.mesh on pjit shardings) on top of whatever the caller
    declared up front."""

    def __init__(self, report: CommReport, fabrics: Dict[str, str],
                 dcn_axes: Optional[Sequence[str]],
                 reshard_min: int, replicated_min: int):
        from jax.extend import core
        self._var = core.Var
        self.report = report
        self.fabrics = fabrics          # axis -> "ici" | "dcn"
        self.dcn_axes = dcn_axes
        self.reshard_min = reshard_min
        self.replicated_min = replicated_min
        self._seen_meshes: Set[int] = set()

    # -- mesh discovery -----------------------------------------------------

    def adopt_mesh(self, mesh: Any) -> None:
        if mesh is None or id(mesh) in self._seen_meshes:
            return
        self._seen_meshes.add(id(mesh))
        try:
            shape = dict(mesh.shape)
        except Exception:
            shape = {}
        for axis, size in shape.items():
            if isinstance(axis, str):
                self.report.axes_declared.setdefault(axis, int(size))
        for axis, kind in classify_mesh_axes(mesh, self.dcn_axes).items():
            # DCN wins: one mesh placing the axis across hosts taints it.
            if self.fabrics.get(axis) != "dcn":
                self.fabrics[axis] = kind

    def _group_size(self, axes: Sequence[str]) -> int:
        g = 1
        for axis in axes:
            g *= max(int(self.report.axes_declared.get(axis, 1)), 1)
        return g

    def _is_dcn(self, axes: Iterable[str]) -> bool:
        return any(self.fabrics.get(a) == "dcn" for a in axes)

    # -- per-eqn handlers ---------------------------------------------------

    def _record_collective(self, eqn, mult: int) -> None:
        from .jaxpr_check import _axis_names, _payload_bytes, collective_name
        name = collective_name(eqn)
        axes = _axis_names(eqn.params)
        payload = _payload_bytes(eqn)
        # Wire bytes: payload x communicator group size (the all-gather/
        # reduce upper bound); ppermute/pshuffle rotate the payload one
        # hop, so the group size does not multiply.
        group = 1 if name in ("ppermute", "pshuffle") \
            else self._group_size(axes)
        wire = payload * group
        dcn = self._is_dcn(axes)
        entry = self.report.by_primitive.setdefault(
            name, {"count": 0, "bytes": 0, "wire_bytes": 0, "dcn_bytes": 0})
        entry["count"] += mult
        entry["bytes"] += mult * payload
        entry["wire_bytes"] += mult * wire
        if dcn:
            entry["dcn_bytes"] += mult * wire
        self.report.total_wire_bytes += mult * wire
        if dcn:
            self.report.dcn_wire_bytes += mult * wire
        fabrics_named = set()
        for axis in axes:
            self.report.axes_used.add(axis)
            fabric = self.fabrics.get(axis, "ici")
            fabrics_named.add(fabric)
            ax = self.report.by_axis.setdefault(
                axis, {"fabric": fabric, "size":
                       int(self.report.axes_declared.get(axis, 1)),
                       "count": 0, "wire_bytes": 0})
            ax["fabric"] = fabric
            ax["count"] += mult
            ax["wire_bytes"] += mult * wire
            # HVD403a: the axis is not on any discovered mesh — the
            # static form of reducing over a process set that does not
            # exist in this deployment.
            if self.report.axes_declared and \
                    axis not in self.report.axes_declared:
                self._emit(
                    "HVD403",
                    f"collective '{name}' communicates over axis "
                    f"'{axis}' but the mesh only declares "
                    f"{sorted(self.report.axes_declared)} — no process "
                    f"set carries that axis in this deployment")
        # HVD403b: one flat collective spanning both fabrics — the whole
        # payload crosses process-set scopes at DCN speed instead of the
        # hierarchical ICI-then-DCN decomposition.
        if "ici" in fabrics_named and "dcn" in fabrics_named:
            self._emit(
                "HVD403",
                f"collective '{name}' mixes ICI and DCN axes "
                f"{sorted(axes)} in one flat communicator — the full "
                f"{payload}-byte payload moves at DCN speed; decompose "
                f"hierarchically (ICI axis first, then the DCN axis)")

    def _handle_pjit(self, eqn, known: Dict[Any, Optional[Tuple]],
                     mult: int) -> None:
        in_sh = eqn.params.get("in_shardings") or ()
        out_sh = eqn.params.get("out_shardings") or ()
        sharded_peer_axes: Set[str] = set()
        expected: List[Optional[Tuple]] = []
        for v, s in zip(eqn.invars, in_sh):
            self.adopt_mesh(getattr(s, "mesh", None))
            ndim = len(getattr(getattr(v, "aval", None), "shape", ()))
            key = _spec_key(s, ndim)
            expected.append(key)
            axes = _key_axes(key)
            self.report.axes_used.update(axes)
            sharded_peer_axes.update(axes)
        for v, key in zip(eqn.invars, expected):
            if key is None or not isinstance(v, self._var):
                continue
            prev = known.get(v)
            # HVD400: produced under one sharding, consumed under
            # another — GSPMD inserts the transfer implicitly.
            if prev is not None and prev != key:
                b = _aval_bytes(v.aval)
                if b >= self.reshard_min:
                    moved_axes = _key_axes(prev) | _key_axes(key)
                    self.report.reshard_bytes += mult * b
                    self.report.total_wire_bytes += mult * b
                    if self._is_dcn(moved_axes):
                        self.report.dcn_wire_bytes += mult * b
                    self.report.reshard_events.append({
                        "from": _fmt_key(prev), "to": _fmt_key(key),
                        "bytes": int(b),
                        "shape": list(getattr(v.aval, "shape", ())),
                        "dtype": str(getattr(v.aval, "dtype", "?"))})
                    self._emit(
                        "HVD400",
                        f"implicit resharding: a "
                        f"{str(getattr(v.aval, 'dtype', '?'))}"
                        f"{tuple(getattr(v.aval, 'shape', ()))} value "
                        f"produced under {_fmt_key(prev)} is consumed "
                        f"under {_fmt_key(key)} — GSPMD inserts a "
                        f"~{b}-byte transfer; reshard once explicitly "
                        f"(with_sharding_constraint) or align the specs")
            # HVD402: a large fully-replicated operand riding next to
            # sharded peers — a declared axis that divides its leading
            # dim would shard it instead of mailing every shard a copy.
            if prev is None and key is not None and not _key_axes(key):
                b = _aval_bytes(v.aval)
                shape = tuple(getattr(v.aval, "shape", ()))
                if b >= self.replicated_min and shape:
                    for axis in sorted(sharded_peer_axes):
                        size = self.report.axes_declared.get(axis, 0)
                        if size > 1 and shape[0] % size == 0:
                            self._emit(
                                "HVD402",
                                f"replicated operand "
                                f"{str(getattr(v.aval, 'dtype', '?'))}"
                                f"{shape} ({b} bytes) rides next to "
                                f"peers sharded over '{axis}' (size "
                                f"{size}, which divides dim 0) — "
                                f"sharding it saves "
                                f"{b - b // size} bytes per device")
                            break
        for v, s in zip(eqn.outvars, out_sh):
            self.adopt_mesh(getattr(s, "mesh", None))
            ndim = len(getattr(getattr(v, "aval", None), "shape", ()))
            key = _spec_key(s, ndim)
            if key is not None:
                known[v] = key
                self.report.axes_used.update(_key_axes(key))

    # -- the walk -----------------------------------------------------------

    def _emit(self, rule: str, message: str) -> None:
        self.report.findings.append(Finding(
            rule=rule, path=self.report.label, line=0, col=0,
            message=message, source="comm"))

    def walk(self, jaxpr, mult: int = 1,
             known: Optional[Dict[Any, Optional[Tuple]]] = None) -> None:
        from .jaxpr_check import _as_jaxpr, _sub_jaxprs, collective_name
        j = _as_jaxpr(jaxpr)
        if j is None:
            return
        known = {} if known is None else known
        for eqn in j.eqns:
            name = eqn.primitive.name
            if collective_name(eqn) is not None:
                self._record_collective(eqn, mult)
            elif name == "jit":
                self._handle_pjit(eqn, known, mult)
                self.walk(eqn.params.get("jaxpr"), mult)
            elif name == "sharding_constraint":
                # The deliberate-resharding idiom: the author asked for
                # this layout — update the value's sharding, no finding.
                s = eqn.params.get("sharding")
                self.adopt_mesh(getattr(s, "mesh", None))
                for v in eqn.outvars:
                    ndim = len(getattr(getattr(v, "aval", None),
                                       "shape", ()))
                    key = _spec_key(s, ndim)
                    if key is not None:
                        known[v] = key
                        self.report.axes_used.update(_key_axes(key))
            elif name == "shard_map":
                self.adopt_mesh(eqn.params.get("mesh"))
                for names in (eqn.params.get("in_names") or (),
                              eqn.params.get("out_names") or ()):
                    self.report.axes_used.update(
                        a for a in _axis_strings(names)
                        if isinstance(a, str))
                self.walk(eqn.params.get("jaxpr"), mult)
            elif name == "cond":
                for b in eqn.params.get("branches", ()):
                    self.walk(b, mult)
            elif name == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                self.walk(eqn.params.get("jaxpr"), mult * length)
            elif name in ("while", "while_loop"):
                self.report.dynamic_loops += 1
                self.walk(eqn.params.get("cond_jaxpr"), mult)
                self.walk(eqn.params.get("body_jaxpr"), mult)
            else:
                for sub in _sub_jaxprs(eqn):
                    self.walk(sub, mult)


def measure_closed_jaxpr_comm(closed_jaxpr, *,
                              label: str = "<jaxpr>",
                              mesh: Any = None,
                              axis_sizes: Optional[Dict[str, int]] = None,
                              dcn_axes: Optional[Sequence[str]] = None,
                              budget_bytes: Optional[int] = None,
                              dcn_budget: Optional[int] = None,
                              reshard_min_bytes: int = RESHARD_MIN_BYTES,
                              replicated_min_bytes: int =
                              REPLICATED_MIN_BYTES) -> CommReport:
    """Sharding/communication-walk an already-traced program.

    ``mesh`` (the deployment's Mesh, when the caller has it — shard_step
    passes its own) seeds the declared axes and the ICI/DCN fabric map;
    ``axis_sizes`` seeds bare axis extents for axis_env-traced programs
    (DistributedOptimizer's eager path).  The walk itself discovers
    meshes on shard_map eqns and NamedShardings, so both are optional.
    ``budget_bytes``/``dcn_budget`` default to the
    ``HVD_COMM_BUDGET_BYTES``/``HVD_COMM_DCN_BUDGET_BYTES`` knobs; when
    known, HVD401 fires on overshoot."""
    report = CommReport(label=label)
    if axis_sizes:
        for axis, size in axis_sizes.items():
            if isinstance(axis, str):
                report.axes_declared[axis] = int(size)
    walker = _CommWalker(report, fabrics={}, dcn_axes=dcn_axes,
                         reshard_min=reshard_min_bytes,
                         replicated_min=replicated_min_bytes)
    if dcn_axes is None:
        forced = dcn_axes_override()
    else:
        forced = tuple(dcn_axes)
    for axis in forced:
        if axis in report.axes_declared or mesh is None:
            walker.fabrics[axis] = "dcn"
    walker.adopt_mesh(mesh)
    walker.walk(closed_jaxpr, 1)

    # HVD404: declared-but-never-communicated axes — chips reserved for
    # a parallelism dimension the program never exercises.
    for axis, size in sorted(report.axes_declared.items()):
        if size > 1 and axis not in report.axes_used:
            report.findings.append(Finding(
                rule="HVD404", path=label, line=0, col=0, source="comm",
                message=f"mesh axis '{axis}' (size {size}) is never "
                        f"named by a collective or a sharding spec — "
                        f"dead parallelism: {size}x the chips for 1x "
                        f"the work; drop the axis or shard over it"))

    budget = budget_bytes if budget_bytes is not None else \
        comm_budget_bytes()
    report.budget_bytes = budget
    if budget is not None:
        report.headroom_bytes = int(budget) - int(report.total_wire_bytes)
        if report.headroom_bytes < 0:
            report.findings.append(Finding(
                rule="HVD401", path=label, line=0, col=0, source="comm",
                message=f"estimated per-step wire bytes "
                        f"{report.total_wire_bytes} exceed the comm "
                        f"budget {budget} bytes "
                        f"(HVD_COMM_BUDGET_BYTES) by "
                        f"{-report.headroom_bytes} bytes"))
    dbudget = dcn_budget if dcn_budget is not None else dcn_budget_bytes()
    report.dcn_budget_bytes = dbudget
    if dbudget is not None:
        report.dcn_headroom_bytes = \
            int(dbudget) - int(report.dcn_wire_bytes)
        if report.dcn_headroom_bytes < 0:
            report.findings.append(Finding(
                rule="HVD401", path=label, line=0, col=0, source="comm",
                message=f"estimated per-step DCN wire bytes "
                        f"{report.dcn_wire_bytes} exceed the DCN "
                        f"sub-budget {dbudget} bytes "
                        f"(HVD_COMM_DCN_BUDGET_BYTES) by "
                        f"{-report.dcn_headroom_bytes} bytes — the "
                        f"cross-host fabric is the slow one"))
    return report


def measure_step_fn_comm(fn, args: Sequence[Any] = (),
                         kwargs: Optional[dict] = None, *,
                         label: Optional[str] = None,
                         axis_env: Optional[Sequence[Tuple[str, int]]] =
                         None,
                         **measure_kwargs) -> CommReport:
    """Trace ``fn(*args, **kwargs)`` and comm-walk it.  Trace failures
    come back as an empty report (the jaxpr checker owns trace-failure
    reporting, HVD100)."""
    import jax
    name = label or getattr(fn, "__name__", None) or "step"
    kw = kwargs or {}
    try:
        traced = jax.make_jaxpr(
            lambda *a: fn(*a, **kw),
            axis_env=[tuple(e) for e in axis_env] if axis_env else None,
        )(*args)
    except Exception:
        return CommReport(label=name)
    sizes = dict(axis_env) if axis_env else None
    return measure_closed_jaxpr_comm(traced, label=name,
                                     axis_sizes=sizes, **measure_kwargs)


# ---------------------------------------------------------------------------
# Replica-plan go/no-go (the serve layer's admission primitive)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanVerdict:
    """One static go/no-go for a replica plan: hvdmem's pool-vs-budget
    verdict (HVD302) combined with the comm budget (HVD401)."""

    label: str
    go: bool
    mem: dict
    comm: dict
    findings: List[Finding] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {"label": self.label, "go": self.go,
                "mem": self.mem, "comm": self.comm,
                "findings": [f.to_dict() for f in self.findings]}


def check_replica_plan(label: str, *,
                       pool_bytes: int = 0,
                       weight_bytes: int = 0,
                       step_comm_bytes: int = 0,
                       step_dcn_bytes: int = 0,
                       mem_budget_bytes: Optional[int] = None,
                       comm_budget: Optional[int] = None,
                       dcn_budget: Optional[int] = None) -> PlanVerdict:
    """Static admission check for one replica plan, BEFORE any traffic:
    does the KV pool + weights fit HBM (hvdmem HVD302), and does the
    per-step decode communication fit the budgets (HVD401, with the
    stricter DCN sub-budget)?  ``go`` is False iff any check fails.

    A data-parallel replica passes trivially (its serve programs census
    zero collectives — the ROADMAP-5 invariant); a tensor/pipeline-
    sharded replica supplies its measured ``CommReport`` bytes.  The
    engine runs this at construction and exposes the verdict on
    ``kv_stats``/``healthz`` (docs/serving.md)."""
    from .memplan import check_pool_budget
    mem = check_pool_budget(label, pool_bytes, weight_bytes,
                            budget=mem_budget_bytes)
    comm = CommReport(label=label,
                      total_wire_bytes=int(step_comm_bytes),
                      dcn_wire_bytes=int(step_dcn_bytes))
    budget = comm_budget if comm_budget is not None else comm_budget_bytes()
    comm.budget_bytes = budget
    if budget is not None:
        comm.headroom_bytes = int(budget) - comm.total_wire_bytes
        if comm.headroom_bytes < 0:
            comm.findings.append(Finding(
                rule="HVD401", path=label, line=0, col=0, source="comm",
                message=f"replica plan's per-step wire bytes "
                        f"{comm.total_wire_bytes} exceed the comm "
                        f"budget {budget} bytes (HVD_COMM_BUDGET_BYTES) "
                        f"by {-comm.headroom_bytes} bytes"))
    dbudget = dcn_budget if dcn_budget is not None else dcn_budget_bytes()
    comm.dcn_budget_bytes = dbudget
    if dbudget is not None:
        comm.dcn_headroom_bytes = int(dbudget) - comm.dcn_wire_bytes
        if comm.dcn_headroom_bytes < 0:
            comm.findings.append(Finding(
                rule="HVD401", path=label, line=0, col=0, source="comm",
                message=f"replica plan's per-step DCN bytes "
                        f"{comm.dcn_wire_bytes} exceed the DCN "
                        f"sub-budget {dbudget} bytes "
                        f"(HVD_COMM_DCN_BUDGET_BYTES) by "
                        f"{-comm.dcn_headroom_bytes} bytes"))
    findings = list(mem.findings) + list(comm.findings)
    return PlanVerdict(label=label, go=not findings,
                       mem=mem.to_dict(), comm=comm.to_dict(),
                       findings=findings)


def publish_report(report: CommReport) -> None:
    """Log findings, append to ``core.analysis_reports()``, and chart
    the comm census on the active timeline — the exact surfacing the
    collective/memory censuses use.  Never raises."""
    from ..utils import get_logger
    log = get_logger()
    for f in report.findings:
        log.warning("hvdshard: %s", f.format())
    try:
        from .. import core as _core
        _core._state.analysis_reports.append(report)
        tl = _core._state.timeline
        if tl is not None:
            tl.comm_census(report.label, report.to_dict())
    except Exception as e:  # pragma: no cover - publication is best-effort
        log.warning("hvdshard: could not publish report: %s", e)


def publish_verdict(verdict: PlanVerdict) -> None:
    """Surface a failed (or any) replica-plan verdict the same way a
    trace-time report is surfaced: findings logged as warnings, the
    verdict appended to ``core.analysis_reports()``.  Never raises."""
    from ..utils import get_logger
    log = get_logger()
    for f in verdict.findings:
        log.warning("hvdshard: %s", f.format())
    try:
        from .. import core as _core
        _core._state.analysis_reports.append(verdict)
    except Exception as e:  # pragma: no cover - publication is best-effort
        log.warning("hvdshard: could not publish verdict: %s", e)


# ---------------------------------------------------------------------------
# AST half (the CLI --comm pass): HVD400 / HVD404 source shapes
# ---------------------------------------------------------------------------

_CONSTRAIN_FNS = {"with_sharding_constraint", "device_put"}
_MESH_CTORS = {"Mesh", "make_mesh", "make_hierarchical_mesh"}


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _literal_pspec(node: ast.AST) -> Optional[Tuple]:
    """The canonical key of a literal ``P(...)``/``PartitionSpec(...)``
    call found anywhere inside ``node`` (e.g. bare, or wrapped in
    ``NamedSharding(mesh, P(...))``).  None when there is no literal
    spec — a computed spec makes no static claim."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        if _call_name(sub) not in ("P", "PartitionSpec"):
            continue
        key: List[Optional[Tuple[str, ...]]] = []
        for arg in sub.args:
            if isinstance(arg, ast.Constant) and arg.value is None:
                key.append(None)
            elif isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str):
                key.append((arg.value,))
            elif isinstance(arg, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant) and
                    isinstance(e.value, str) for e in arg.elts):
                key.append(tuple(e.value for e in arg.elts))
            else:
                return None  # partially dynamic: no static claim
        return tuple(key)
    return None


def _mesh_literal_axes(call: ast.Call) -> Optional[List[str]]:
    """Literal axis names of a mesh constructor call: the dict keys of
    ``make_mesh({"x": ..})`` or the string tuple of
    ``Mesh(devs, ("x", "y"))`` / ``axis_names=(...)``.  None when the
    axes are not statically visible."""
    candidates: List[ast.AST] = list(call.args)
    for kw in call.keywords:
        if kw.arg in ("axes", "axis_names", "shape"):
            candidates.insert(0, kw.value)
    for arg in candidates:
        if isinstance(arg, ast.Dict) and arg.keys and all(
                isinstance(k, ast.Constant) and isinstance(k.value, str)
                for k in arg.keys if k is not None):
            return [k.value for k in arg.keys if k is not None]
        if isinstance(arg, (ast.Tuple, ast.List)) and arg.elts and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in arg.elts):
            return [e.value for e in arg.elts]
    return None


def _iter_functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class _CommVisitor:
    """Module walk collecting the HVD400/HVD404 source findings."""

    def __init__(self, path: str):
        self.path = path
        self.findings: List[Finding] = []

    def run(self, tree: ast.Module) -> List[Finding]:
        for fn in _iter_functions(tree):
            self._check_hvd400(fn)
            self._check_hvd404(fn)
        seen: Set[Tuple] = set()
        uniq: List[Finding] = []
        for f in sorted(self.findings,
                        key=lambda f: (f.line, f.col, f.rule)):
            key = (f.rule, f.line, f.col)
            if key not in seen:
                seen.add(key)
                uniq.append(f)
        return uniq

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.path,
            line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0) + 1,
            message=message, source="comm"))

    # -- HVD400: one value annotated with two different literal specs --------

    def _check_hvd400(self, fn: ast.AST) -> None:
        """``with_sharding_constraint(x, P("a"))`` and later
        ``with_sharding_constraint(x, P("b"))`` on the SAME name in one
        function: GSPMD materializes ``x`` under both layouts — one of
        them is an implicit reshard.  Rebinding the constrained result
        (``y = with_sharding_constraint(x, ...)``, then using ``y``) is
        the deliberate-resharding idiom and stays clean."""
        first: Dict[str, Tuple[Tuple, ast.Call]] = {}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) not in _CONSTRAIN_FNS:
                continue
            target = node.args[0]
            if not isinstance(target, ast.Name):
                continue
            if len(node.args) < 2 and not node.keywords:
                continue
            spec_src = node.args[1] if len(node.args) > 1 else node
            key = _literal_pspec(spec_src)
            if key is None:
                continue
            prev = first.get(target.id)
            if prev is None:
                first[target.id] = (key, node)
            elif prev[0] != key:
                self._emit(
                    "HVD400", node,
                    f"'{target.id}' is annotated with "
                    f"{_fmt_key(key)} here but with "
                    f"{_fmt_key(prev[0])} at line {prev[1].lineno} — "
                    f"consuming one value under two shardings makes "
                    f"GSPMD materialize both layouts (an implicit "
                    f"reshard); rebind the constrained result to a new "
                    f"name if the second layout is deliberate")

    # -- HVD404: mesh axis never exercised by this function's specs ---------

    def _check_hvd404(self, fn: ast.AST) -> None:
        """A mesh built from literal axes, consumed in the same function
        whose literal specs exercise SOME of those axes but never one of
        them: the dead axis multiplies chips without parallelizing
        anything.  Meshes that escape (returned / stored on self) are
        skipped — their axes may be used by callers."""
        meshes: List[Tuple[str, List[str], ast.Call]] = []
        escaped: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    _call_name(node.value) in _MESH_CTORS:
                axes = _mesh_literal_axes(node.value)
                if not axes:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        meshes.append((t.id, axes, node.value))
                    else:
                        escaped.add("")  # stored into an attribute etc.
            elif isinstance(node, ast.Return) and node.value is not None:
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Name):
                        escaped.add(sub.id)
        if not meshes:
            return
        mesh_lines = {m[2].lineno for m in meshes}
        used: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    _call_name(node) in ("P", "PartitionSpec") and \
                    getattr(node, "lineno", 0) not in mesh_lines:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        used.add(sub.value)
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis") and \
                            isinstance(kw.value, ast.Constant) and \
                            isinstance(kw.value.value, str):
                        used.add(kw.value.value)
        if not used:
            return  # no literal spec usage at all: no static claim
        for name, axes, call in meshes:
            if name in escaped:
                continue
            dead = [a for a in axes if a not in used]
            if dead and len(dead) < len(axes):
                self._emit(
                    "HVD404", call,
                    f"mesh '{name}' declares axes {axes} but "
                    f"{dead} never appear in any spec or axis_name in "
                    f"this function while {sorted(set(axes) - set(dead))} "
                    f"do — dead parallelism: those chips replicate work")


def analyze_source(source: str, path: str = "<string>",
                   select: Sequence[str] = (),
                   ignore: Sequence[str] = ()) -> List[Finding]:
    """AST --comm pass over one source string (HVD400/HVD404 source
    shapes), honoring the shared hvdlint pragma + select/ignore
    contract."""
    from .linter import _parse_pragmas, _suppressed
    try:
        tree = ast.parse(source, filename=path)
    except (SyntaxError, ValueError, RecursionError) as e:
        if not rule_selected("HVD000", select, ignore):
            return []
        line = getattr(e, "lineno", 0) or 0
        col = (getattr(e, "offset", 0) or 0)
        return [Finding(rule="HVD000", path=path, line=line,
                        col=max(col, 1), source="comm",
                        message=f"could not parse: {type(e).__name__}: "
                                f"{e}")]
    findings = _CommVisitor(path).run(tree)
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
    """AST --comm pass over files/directories (the dogfooding command:
    ``python -m horovod_tpu.analysis --comm horovod_tpu examples``)."""
    from .linter import iter_python_files
    findings: List[Finding] = []
    files: List[str] = []
    for path in paths:
        if not os.path.exists(path):
            if rule_selected("HVD000", select, ignore):
                findings.append(Finding(
                    rule="HVD000", path=path, line=0, col=1,
                    source="comm", message="path does not exist"))
        else:
            files.append(path)
    for fpath in iter_python_files(files):
        try:
            with open(fpath, "rb") as fh:
                source = fh.read().decode("utf-8", errors="replace")
        except OSError as e:
            if rule_selected("HVD000", select, ignore):
                findings.append(Finding(
                    rule="HVD000", path=fpath, line=0, col=1,
                    source="comm",
                    message=f"could not read file: {e}"))
            continue
        findings.extend(analyze_source(source, path=fpath, select=select,
                                       ignore=ignore))
    return findings
