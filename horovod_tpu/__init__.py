"""horovod_tpu — a TPU-native distributed training framework with the
capabilities of Horovod (reference: horovod/horovod v0.28.1).

The public surface mirrors the Horovod API (``hvd.init``, ``hvd.rank``,
``hvd.allreduce``, ``hvd.DistributedOptimizer``, elastic state objects,
``horovodrun``) but the architecture is TPU-first (SURVEY.md §7): the data
plane is XLA collectives (psum/all_gather/all_to_all/ppermute) over the ICI
torus inside jit-compiled programs; the host side keeps only the control
plane — topology/rendezvous, process sets, eager negotiation, elastic
membership, timeline, stall inspection.

Typical use (the Horovod idiom, TPU-compiled)::

    import horovod_tpu as hvd
    hvd.init()
    step = hvd.shard_step(train_step)        # SPMD over the chip mesh
    # or eager / Horovod-classic:
    avg_grads = hvd.allreduce(grads, op=hvd.Average)
"""

from .version import __version__  # noqa: F401

from .core import (  # noqa: F401
    init, shutdown, is_initialized,
    rank, size, local_rank, local_size, cross_rank, cross_size,
    num_slots, local_slots, mesh, mesh_axis, is_homogeneous,
    start_timeline, stop_timeline,
    mpi_threads_supported, mpi_enabled, mpi_built,
    gloo_enabled, gloo_built, nccl_built, ddl_built, ccl_built,
    cuda_built, rocm_built, xla_built, xla_enabled,
)

from .ops import (  # noqa: F401
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
    allreduce, allreduce_, allreduce_async, allreduce_async_,
    grouped_allreduce, grouped_allreduce_, grouped_allreduce_async,
    grouped_allreduce_async_,
    allgather, allgather_async, grouped_allgather, grouped_allgather_async,
    broadcast, broadcast_, broadcast_async, broadcast_async_,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async,
    grouped_reducescatter, grouped_reducescatter_async,
    poll, synchronize, barrier, join,
)

from .compression import Compression  # noqa: F401

from .optimizer import (  # noqa: F401
    DistributedOptimizer, distributed_gradient_transformation,
    adasum_delta_step, value_and_grad, grad, local_value_and_grad,
    PartialDistributedOptimizer,
)

from .functions import (  # noqa: F401
    broadcast_variables, broadcast_parameters, broadcast_optimizer_state,
    broadcast_object, broadcast_object_fn, allgather_object,
)

from .sync_batch_norm import SyncBatchNorm, sync_batch_stats  # noqa: F401

from .sparse import sparse_allreduce, densify_if_sparse  # noqa: F401

from . import callbacks  # noqa: F401
from . import checkpoint  # noqa: F401
from . import data  # noqa: F401

from . import parallel  # noqa: F401
from .parallel import shard_step  # noqa: F401  (hvd.shard_step idiom)

from . import runner  # noqa: F401
from . import elastic  # noqa: F401
from . import serve  # noqa: F401  (continuous-batching inference serving)
from . import spark  # noqa: F401
run = runner.run  # launcher API (reference: horovod.run, runner/__init__.py:95)

from .process_sets import (  # noqa: F401
    ProcessSet, global_process_set, add_process_set, remove_process_set,
    get_process_set_ids, partition_process_sets,
)

from .exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt, CollectiveRejectedError,
    RendezvousUnreachableError,
)
