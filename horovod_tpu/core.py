"""Global runtime state + the init/info API surface.

The reference's equivalent is the ``extern "C"`` surface of
horovod/common/operations.cc:932-1405 (``horovod_init``, ``horovod_rank``,
``horovod_size``, ``horovod_local_rank``..., process-set CRUD, built/enabled
queries) reached from Python through the ctypes ``HorovodBasics`` wrapper
(common/basics.py:29,51), plus the background-thread bring-up of
``InitializeHorovodOnce`` (operations.cc:856).

The TPU build needs no background communication thread for the compiled data
plane — collectives live inside XLA programs — so ``init()`` reduces to:
resolve knobs, discover topology, (optionally) join the multi-process runtime
(``jax.distributed.initialize`` — the rendezvous analog of MPI_Init /
Gloo HTTP rendezvous, operations.cc:417-450), build the global device
``Mesh``, and register process sets.  The eager dispatch engine and its
negotiation core (the surviving part of the reference's controller) are
created lazily by ops/eager.py.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import List, Optional, Sequence

import numpy as np

from . import config as _config
from . import topology as _topology
from .utils import get_logger


class _GlobalState:
    """Singleton per process (reference: HorovodGlobalState, global_state.h:39)."""

    def __init__(self):
        self.lock = threading.RLock()
        self.initialized = False
        self.config: Optional[_config.Config] = None
        self.topology: Optional[_topology.Topology] = None
        self.mesh = None
        self.process_set_table = None
        self.eager_engine = None
        self.timeline = None
        self.param_manager = None
        self.elastic_enabled = False
        # JaxprReports published by the HVD_ANALYZE=1 trace-time hook
        # (analysis/hook.py); read via core.analysis_reports().  Survives
        # shutdown so post-run tooling (bench.py) can still read it.
        self.analysis_reports: List = []


_state = _GlobalState()


def _build_mesh(topo: _topology.Topology, cfg: _config.Config):
    import jax
    from jax.sharding import Mesh
    devices = topo.devices if topo.devices else list(jax.devices())
    return Mesh(np.asarray(devices), (cfg.mesh_axis,))


def _autotune_scope() -> str:
    """KV scope for autotune sync, namespaced by the negotiation generation:
    keys from a previous world incarnation (elastic reset) must never feed
    a fresh ParameterManager — a follower reading a stale candidate would
    explore a different fusion threshold than rank 0's new GP run."""
    return f"autotune@{os.environ.get('HVD_TPU_NEGOTIATION_GEN', '0')}"


def _maybe_join_distributed(cfg: _config.Config) -> None:
    """Join the multi-process JAX runtime when launched by horovodrun.

    The launcher injects HOROVOD_RANK/SIZE and the rendezvous address
    (runner/gloo_run.py:66-78 analog); we translate that into
    ``jax.distributed.initialize``, which plays the role of
    MPI_Init_thread / Gloo HTTP rendezvous in BackgroundThreadLoop
    (operations.cc:417-450)."""
    rank = os.environ.get(_config.HOROVOD_RANK)
    size = os.environ.get(_config.HOROVOD_SIZE)
    addr = os.environ.get(_config.HOROVOD_RENDEZVOUS_ADDR)
    port = os.environ.get(_config.HOROVOD_RENDEZVOUS_PORT)
    if rank is None or size is None or int(size) <= 1 or addr is None:
        return
    if os.environ.get("HOROVOD_ELASTIC") == "1":
        # Meet every peer incarnation of this world generation BEFORE
        # touching jax.distributed — a non-converging initialize aborts
        # the process (see elastic._await_world_at_init_barrier).  The
        # barrier may adopt a newer world, so re-read the slot env after.
        from .elastic import _await_world_at_init_barrier
        _await_world_at_init_barrier()
        rank = os.environ.get(_config.HOROVOD_RANK)
        size = os.environ.get(_config.HOROVOD_SIZE)
        if rank is None or size is None or int(size) <= 1:
            return
    # Must not touch the XLA backend (e.g. jax.devices/process_count) before
    # jax.distributed.initialize — probe the distributed client state instead.
    import jax
    from jax._src import distributed as _jdist
    if getattr(_jdist.global_state, "client", None) is not None:
        return  # already initialized by the user
    coordinator = os.environ.get(
        "HVD_TPU_COORDINATOR", f"{addr}:{int(port) + 1 if port else 9999}")
    # Bounded init: an elastic in-place reset can otherwise block the full
    # default 300 s inside initialize() waiting for a peer that is dead and
    # will re-rendezvous into a DIFFERENT world generation.  The elastic
    # retry loop handles the timeout (upgrade to a world refresh).
    init_timeout = int(float(os.environ.get(
        "HVD_TPU_DIST_INIT_TIMEOUT_S",
        os.environ.get(_config.HOROVOD_GLOO_TIMEOUT_SECONDS, "300"))))
    # A dead peer makes jax.distributed.shutdown's barrier hang the full
    # shutdown timeout before the client aborts the process; bound it so a
    # doomed survivor dies (and gets respawned into a fresh world) quickly.
    # Healthy same-world resets clear the barrier in well under a second.
    shutdown_timeout = int(float(os.environ.get(
        "HVD_TPU_DIST_SHUTDOWN_TIMEOUT_S", "60")))
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(size),
        process_id=int(rank),
        initialization_timeout=init_timeout,
        shutdown_timeout_seconds=shutdown_timeout,
    )


def _enable_compile_cache() -> None:
    """The one persistent XLA compile cache of this checkout: elastic
    resizes, relaunches and restarted servers re-trace every program
    (SURVEY.md §7 "hide latency with compilation cache"), and with the
    cache the re-compile is a disk hit.  Where ``JAX_COMPILATION_CACHE_DIR``
    places it from outside, JAX reads that itself; otherwise it lives at a
    fixed path — the path is part of the cache's key, so one that moves
    never hits.  The floors go to zero so that the small, fast-compiling
    serve bucket programs persist too."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def init(comm: Optional[Sequence[int]] = None,
         process_sets=None) -> None:
    """Initialize the runtime (hvd.init analog, operations.cc:934 horovod_init).

    Args:
      comm: optional list of global ranks participating (reference: the
        ``ranks`` argument of horovod_init restricting the global communicator).
        Unsupported values raise — on TPU the job membership is fixed by the
        launcher/slice, matching horovod_init_multi_comm's constraints.
      process_sets: optional list of ``ProcessSet`` objects to register at
        init, like hvd.init(process_sets=[...]) (common/basics.py:51).
    """
    from . import process_sets as _ps

    with _state.lock:
        if _state.initialized:
            return
        from .analysis import hook as _analysis_hook
        if _analysis_hook.enabled():
            # Fresh world ⇒ fresh first-compile analysis generation: an
            # elastic re-init compiles new programs that deserve their own
            # check (analysis/hook.py generation()).
            _analysis_hook.reset()
            _state.analysis_reports = []
        cfg = _config.Config.from_env()
        _enable_compile_cache()
        _maybe_join_distributed(cfg)
        topo = _topology.detect(cfg)
        if comm is not None and list(comm) != list(range(topo.size)):
            raise ValueError(
                "horovod_tpu.init(comm=...) with a strict subset of ranks is "
                "not supported on TPU; use process sets instead "
                "(process_sets.add_process_set)")
        _state.config = cfg
        _state.topology = topo
        _state.mesh = _build_mesh(topo, cfg)
        _state.process_set_table = _ps.ProcessSetTable(topo.num_slots)
        if process_sets:
            for ps in process_sets:
                _state.process_set_table.register(ps)
        from .autotune import ParameterManager

        def _synced_decision(local_choice: int) -> int:
            """SynchronizeParameters: rank 0's converged threshold wins
            everywhere (rank-divergent thresholds would produce divergent
            fusion buckets → mismatched collectives)."""
            addr = os.environ.get(_config.HOROVOD_RENDEZVOUS_ADDR)
            port = os.environ.get(_config.HOROVOD_RENDEZVOUS_PORT)
            if topo.size <= 1 or topo.emulated or not addr or not port:
                return local_choice
            import json as _json
            import time as _time
            from .runner.http_server import KVStoreClient
            client = KVStoreClient(addr, int(port))
            scope = _autotune_scope()
            if topo.rank == 0:
                client.put(scope, "threshold",
                           _json.dumps({"threshold": local_choice}).encode())
                return local_choice
            deadline = _time.time() + 60
            while _time.time() < deadline:
                raw = client.get(scope, "threshold")
                if raw is not None:
                    return int(_json.loads(raw)["threshold"])
                _time.sleep(0.05)
            return local_choice

        search = cfg.autotune_search
        candidate_pub = candidate_fetch = None
        if cfg.autotune and search == "bayes" and topo.size > 1 and \
                not topo.emulated:
            # Multi-controller BO: rank 0 owns the GP and publishes each
            # round's exploration candidate through the rendezvous KV;
            # followers fetch it, so fusion buckets stay identical on
            # every rank (the reference's rank-0-tunes +
            # SynchronizeParameters design, parameter_manager.h).
            addr = os.environ.get(_config.HOROVOD_RENDEZVOUS_ADDR)
            port = os.environ.get(_config.HOROVOD_RENDEZVOUS_PORT)
            if not addr or not port:
                get_logger().warning(
                    "HOROVOD_AUTOTUNE_SEARCH=bayes needs the rendezvous KV "
                    "to sync candidates; falling back to the sweep")
                search = "sweep"
            else:
                import json as _json
                import time as _time
                from .runner.http_server import KVStoreClient
                _cli = KVStoreClient(addr, int(port))
                _scope = _autotune_scope()
                if topo.rank == 0:
                    def candidate_pub(round_, value):
                        _cli.put(_scope, f"cand/{round_}",
                                 _json.dumps(value).encode())
                else:
                    def candidate_fetch(round_):
                        deadline = _time.time() + 120
                        while _time.time() < deadline:
                            raw = _cli.get(_scope, f"cand/{round_}")
                            if raw is not None:
                                return float(_json.loads(raw))
                            _time.sleep(0.05)
                        from .exceptions import HorovodInternalError
                        raise HorovodInternalError(
                            f"timed out fetching autotune candidate for "
                            f"round {round_} from rank 0")
        _state.param_manager = ParameterManager(
            enabled=cfg.autotune,
            initial_threshold=cfg.fusion_threshold_bytes,
            log_path=cfg.autotune_log if topo.rank == 0 else None,
            decide_fn=_synced_decision,
            search=search,
            bayes_rounds=cfg.autotune_bayes_rounds,
            candidate_pub=candidate_pub,
            candidate_fetch=candidate_fetch)
        if cfg.timeline_path and topo.rank == 0:
            # Rank 0 writes the trace, like the reference coordinator
            # (HOROVOD_TIMELINE, operations.cc:1077).
            from .timeline import Timeline
            _state.timeline = Timeline(cfg.timeline_path,
                                       mark_cycles=cfg.timeline_mark_cycles,
                                       rank=topo.rank)
        _state.initialized = True
        get_logger().info(
            "horovod_tpu initialized: rank=%d size=%d local=%d/%d cross=%d/%d "
            "slots=%d mesh=%s", topo.rank, topo.size, topo.local_rank,
            topo.local_size, topo.cross_rank, topo.cross_size, topo.num_slots,
            tuple(_state.mesh.shape.items()))


def shutdown() -> None:
    """Tear down (horovod_shutdown, operations.cc)."""
    with _state.lock:
        if not _state.initialized:
            return
        if _state.timeline is not None:
            _state.timeline.close()
            _state.timeline = None
        _state.initialized = False
        _state.mesh = None
        _state.topology = None
        _state.process_set_table = None
        eng = _state.eager_engine
        if eng is not None and eng._negotiator is not None:
            eng._negotiator.close()  # stop flusher, ship pending records
        _state.eager_engine = None


atexit.register(shutdown)


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise ValueError(
            "horovod_tpu has not been initialized; call horovod_tpu.init() "
            "first (reference error string: operations.cc horovod_rank)")
    return _state


def is_initialized() -> bool:
    """horovod_is_initialized (operations.cc)."""
    return _state.initialized


def analysis_reports() -> list:
    """JaxprReports from the HVD_ANALYZE=1 trace-time checker (newest
    last).  Empty unless HVD_ANALYZE was set when step programs first
    compiled; see docs/static_analysis.md."""
    return list(_state.analysis_reports)


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Runtime timeline start (horovod_start_timeline, operations.cc:1077)."""
    from .timeline import Timeline
    st = _require_init()
    if st.timeline is not None:
        st.timeline.close()
    st.timeline = Timeline(file_path, mark_cycles=mark_cycles,
                           rank=st.topology.rank)


def stop_timeline() -> None:
    """horovod_stop_timeline."""
    st = _require_init()
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None


def rank() -> int:
    """Global process rank (horovod_rank, operations.cc:1000)."""
    return _require_init().topology.rank


def size() -> int:
    """Global number of ranks (horovod_size)."""
    return _require_init().topology.size


def local_rank() -> int:
    """Rank within the node (horovod_local_rank)."""
    return _require_init().topology.local_rank


def local_size() -> int:
    """Ranks on this node (horovod_local_size)."""
    return _require_init().topology.local_size


def cross_rank() -> int:
    """Node index (horovod_cross_rank)."""
    return _require_init().topology.cross_rank


def cross_size() -> int:
    """Number of nodes (horovod_cross_size)."""
    return _require_init().topology.cross_size


def num_slots() -> int:
    """Total accelerator chips in the job — the mesh axis size.

    TPU extension: the reference's process==GPU identity splits on TPU where
    one process drives several chips; gradient averaging divides by this."""
    return _require_init().topology.num_slots


def local_slots() -> int:
    return _require_init().topology.local_slots


def mesh():
    """The global device mesh (jax.sharding.Mesh) over every chip."""
    return _require_init().mesh


def mesh_axis() -> str:
    return _require_init().config.mesh_axis


def is_homogeneous() -> bool:
    """horovod_is_homogeneous (operations.cc): equal slots per node."""
    return _require_init().topology.is_homogeneous


# ---------------------------------------------------------------------------
# Built/enabled feature queries (operations.cc:1050-1140 horovod_*_built /
# horovod_*_enabled).  The TPU build has exactly one backend — XLA collectives
# — so the legacy backend queries answer False and xla answers True; they are
# kept so reference scripts probing capabilities keep running.
# ---------------------------------------------------------------------------

def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """TPU build: the XLA-collective backend is always present."""
    return True


def xla_enabled() -> bool:
    return True
