"""horovod_tpu.serve — continuous-batching inference serving over the
data-parallel mesh.

The training stack (ring/flash attention, elastic, autotune, hvdlint)
ends at the optimizer step; this subsystem opens the serving workload on
the same machinery: compiled step functions (per-bucket prefill + one
decode program), ``process_sets`` replica groups, ``elastic/preemption``
rank-loss reports, and ``timeline`` counters.

Layers (docs/serving.md has the architecture):

* :mod:`blocks`  — paged KV block pool, per-sequence block tables,
  full-block prefix cache with copy-on-write;
* :mod:`paged_attention` — fused Pallas paged-attention kernels over the
  block tables + int8/fp8 KV block quantization (``HVD_SERVE_ATTN_IMPL``
  / ``HVD_SERVE_KV_DTYPE``);
* :mod:`engine`  — paged KV cache, chunked prefill, iteration-level
  decode loop;
* :mod:`batcher` — bounded queue, size/deadline triggers, QoS tiers +
  EDF ordering, shape buckets, block-budget admission;
* :mod:`replica` — process-set replicas, least-loaded routing, failover;
* :mod:`controller` — hvdctl: SLO-aware autoscaling + the brownout
  ladder (docs/serving.md control plane);
* :mod:`tenancy`  — hvdtenant: per-tenant quotas + weighted
  deficit-round-robin fairness under the QoS ordering;
* :mod:`registry` — hvdtenant: named model variants (full weights or
  adapter deltas), variant routing, live rolling weight swap;
* :mod:`tiering`  — hvdtier: tiered KV hierarchy (device → host RAM →
  KV-server), ahead-of-decode prefetch, cross-replica prefix-block
  migration via the fleet block directory;
* :mod:`server`  — HTTP ``/generate`` ``/healthz`` ``/metrics`` +
  ``hvdserve`` CLI;
* :mod:`router` / :mod:`router_server` — hvdroute: the fault-tolerant
  prefix-affinity front door over N serve endpoints (consistent-hash
  affinity, deadline-bounded retries, tail hedging, ejection/half-open
  readmission, graceful drain — docs/serving.md front door);
* :mod:`metrics` — TTFT / per-token histograms, occupancy, tokens/s.

Quickstart (CPU-exercisable end to end)::

    import horovod_tpu as hvd
    from horovod_tpu.serve import build_replicas, ServeServer
    hvd.init()
    sched = build_replicas(make_adapter, num_replicas=2)
    port = ServeServer(sched).start(port=8000)
    # curl -d '{"tokens": [1,2,3], "max_new_tokens": 8}' :8000/generate
"""

# Lock-witness sanitizer (HVD_SANITIZE=1, analysis/witness.py): install
# BEFORE the submodule imports below so every serve-plane lock — batcher
# condition, engine slot table, metrics, scheduler, block pool — is
# constructed through the instrumented factory.  One env read when off.
from ..analysis import witness as _witness  # noqa: E402

_witness.maybe_install_from_env()

from .batcher import (  # noqa: F401,E402
    DeadlineExceededError, DynamicBatcher, QueueFullError, Request,
    prompt_bucket,
)
from .blocks import (  # noqa: F401
    BlockManager, NoFreeBlocksError, chain_hashes,
)
from .controller import (  # noqa: F401
    ControllerConfig, ControllerState, FleetController, FleetSnapshot,
)
from .engine import (  # noqa: F401
    InferenceEngine, MLPAdapter, ModelAdapter, TransformerAdapter,
)
from .metrics import Histogram, ServeMetrics  # noqa: F401
from .sampling import (  # noqa: F401
    filtered_probs, sample_host, seq_key, token_key, validate_params,
)
from .paged_attention import (  # noqa: F401
    KV_DTYPES, dequantize_kv, kv_bytes_per_token, paged_attention_reference,
    paged_decode_attention, paged_prefill_attention, quantize_kv,
)
from .registry import (  # noqa: F401
    ModelRegistry, ModelVariant, apply_delta, model_salt,
)
from .replica import (  # noqa: F401
    NoHealthyReplicaError, Replica, ReplicaScheduler, build_replicas,
)
from .router import (  # noqa: F401
    Router, RouterConfig, RouterMetrics,
)
from .router_server import RouterServer  # noqa: F401
from .server import (  # noqa: F401
    DrainingThreadingHTTPServer, ServeServer, arm_signal_event,
    run_commandline, serve_until_signal,
)
from .tenancy import (  # noqa: F401
    DeficitRoundRobin, TenantAccounting, TenantConfig, safe_tenant,
)
from .tiering import (  # noqa: F401
    HostTier, TierClient, TierConfig, TieredBlockManager, TierWorker,
)
