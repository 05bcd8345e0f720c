"""Typed configuration / knob system.

The reference exposes every tunable through three equivalent layers that all
resolve to ``HOROVOD_*`` environment variables (knob names in
horovod/common/common.h:116-150, read once in BackgroundThreadLoop,
operations.cc:459-650; CLI flags mapped by runner/launch.py:158-243 and the YAML
config file by runner/common/util/config_parser.py).  This module keeps the same
contract: one typed ``Config`` dataclass, populated from the environment with
the reference's knob names (so existing Horovod job scripts keep working), and
override helpers used by the ``horovodrun``-equivalent CLI.

Precedence (same as reference): explicit runtime API > CLI flag (exported as env
by the launcher) > environment > default.

Defaults mirror the reference: fusion threshold 128 MB (operations.cc:519),
cycle time 1 ms (0 under the compiled/XLA path, operations.cc:528-534), response
cache capacity 1024, stall-check warning at 60 s (stall_inspector.h:78).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Knob names preserved from the reference (common.h:116-150 and runner/launch.py).
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
HOROVOD_TORUS_ALLREDUCE = "HOROVOD_TORUS_ALLREDUCE"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_LOG_HIDE_TIME = "HOROVOD_LOG_HIDE_TIME"  # reference: logging.cc:85
HOROVOD_DYNAMIC_PROCESS_SETS = "HOROVOD_DYNAMIC_PROCESS_SETS"
HOROVOD_DISABLE_GROUP_FUSION = "HOROVOD_DISABLE_GROUP_FUSION"
HOROVOD_ELASTIC_TIMEOUT = "HOROVOD_ELASTIC_TIMEOUT"
HOROVOD_GLOO_TIMEOUT_SECONDS = "HOROVOD_GLOO_TIMEOUT_SECONDS"
# Rendezvous / rank env injected by the launcher (runner/gloo_run.py:66-78,
# common/gloo/gloo_context.h:28-42).
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_HOSTNAME = "HOROVOD_HOSTNAME"
HOROVOD_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
# TPU-build specific knobs (new; no reference analog).
HVD_TPU_EMULATE_RANKS = "HVD_TPU_EMULATE_RANKS"  # treat N local devices as N ranks
HVD_TPU_MESH_AXIS = "HVD_TPU_MESH_AXIS"          # mesh axis name, default "hvd"
HOROVOD_AUTOTUNE_SEARCH = "HOROVOD_AUTOTUNE_SEARCH"      # 'sweep' | 'bayes'
HOROVOD_AUTOTUNE_BAYES_ROUNDS = "HOROVOD_AUTOTUNE_BAYES_ROUNDS"


def env_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def env_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return int(val)
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return float(val)
    except ValueError:
        return default


@dataclasses.dataclass
class Config:
    """All runtime knobs, resolved once at ``init()`` time."""

    # Fusion / cycle (operations.cc:519, :528-534).
    fusion_threshold_bytes: int = 128 * 1024 * 1024
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    # Topology-shaped reduction modes. On TPU these select ICI-native layouts
    # rather than separate software algorithms (nccl_operations.h:231,253).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    torus_allreduce: bool = False
    # Autotune (parameter_manager.h:42-110).
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_search: str = "sweep"   # 'bayes' = GP + expected improvement
    autotune_bayes_rounds: int = 12
    # Timeline (timeline.h:48,108).
    timeline_path: Optional[str] = None
    timeline_mark_cycles: bool = False
    # Stall inspector (stall_inspector.h:30,78).
    stall_check_enabled: bool = True
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    # Process sets (process_set.h:89).
    dynamic_process_sets: bool = False
    disable_group_fusion: bool = False
    # Elastic.
    elastic_timeout_seconds: float = 600.0
    # Logging.
    log_level: str = "warning"
    log_hide_timestamp: bool = False
    # TPU-specific.
    emulate_ranks: int = 0
    mesh_axis: str = "hvd"

    @classmethod
    def from_env(cls) -> "Config":
        return cls(
            fusion_threshold_bytes=env_int(
                HOROVOD_FUSION_THRESHOLD, 128 * 1024 * 1024),
            cycle_time_ms=env_float(HOROVOD_CYCLE_TIME, 1.0),
            cache_capacity=env_int(HOROVOD_CACHE_CAPACITY, 1024),
            hierarchical_allreduce=env_bool(HOROVOD_HIERARCHICAL_ALLREDUCE),
            hierarchical_allgather=env_bool(HOROVOD_HIERARCHICAL_ALLGATHER),
            torus_allreduce=env_bool(HOROVOD_TORUS_ALLREDUCE),
            autotune=env_bool(HOROVOD_AUTOTUNE),
            autotune_log=os.environ.get(HOROVOD_AUTOTUNE_LOG),
            autotune_search=os.environ.get(HOROVOD_AUTOTUNE_SEARCH, "sweep"),
            autotune_bayes_rounds=env_int(HOROVOD_AUTOTUNE_BAYES_ROUNDS, 12),
            timeline_path=os.environ.get(HOROVOD_TIMELINE),
            timeline_mark_cycles=env_bool(HOROVOD_TIMELINE_MARK_CYCLES),
            stall_check_enabled=not env_bool(HOROVOD_STALL_CHECK_DISABLE),
            stall_warning_time_seconds=env_float(
                HOROVOD_STALL_CHECK_TIME_SECONDS, 60.0),
            stall_shutdown_time_seconds=env_float(
                HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0),
            dynamic_process_sets=env_bool(HOROVOD_DYNAMIC_PROCESS_SETS),
            disable_group_fusion=env_bool(HOROVOD_DISABLE_GROUP_FUSION),
            elastic_timeout_seconds=env_float(HOROVOD_ELASTIC_TIMEOUT, 600.0),
            log_level=os.environ.get(HOROVOD_LOG_LEVEL, "warning"),
            log_hide_timestamp=env_bool(HOROVOD_LOG_HIDE_TIME),
            emulate_ranks=env_int(HVD_TPU_EMULATE_RANKS, 0),
            mesh_axis=os.environ.get(HVD_TPU_MESH_AXIS, "hvd"),
        )
