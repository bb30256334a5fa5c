"""horovod_tpu: a TPU-native distributed training framework.

A ground-up rebuild of Horovod 0.16 (reference: SinestroEdmonce/horovod) for
TPU: same user surface — ``init()/rank()/size()``, named async
allreduce/allgather/broadcast with tensor fusion, ``DistributedOptimizer``,
parameter/optimizer-state broadcast, compression, timeline, autotune,
launcher — with the data plane rebuilt on XLA collectives over an ICI/DCN
device mesh instead of MPI/NCCL, and the SPMD compiler replacing the
coordinator for jit-compiled training steps (see SURVEY.md §7).

Typical use, mirroring the reference README:

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    opt = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.num_devices()),
                                   axis_name="data")
    # ... shard_map/pjit train step over the mesh; psum rides ICI ...
    params = hvd.broadcast_parameters(params, root_rank=0)
"""

import os as _os

# HOROVOD_PLATFORM: pin the JAX platform before ANY backend starts.
# Applied at import so launcher-spawned workers — which import this
# package before their first device query — are steered without code
# changes; see docs/running.md.
from .core import config as _config

_platform = _os.environ.get(_config.HOROVOD_PLATFORM)
if _platform:
    import jax as _jax

    _jax.config.update("jax_platforms", _platform)
    # Diagnose the one case the pin cannot fix: a live backend.
    # backends_are_initialized() is the purpose-built passive query
    # (jax.config itself uses it to validate late config changes); there
    # is no fully-public equivalent that doesn't itself initialize a
    # backend.
    _live = _jax._src.xla_bridge.backends_are_initialized()
    if _live:
        import warnings as _warnings

        _warnings.warn(
            f"HOROVOD_PLATFORM={_platform!r} was applied AFTER a JAX "
            f"backend initialized; existing computations stay on the old "
            f"platform. Import horovod_tpu (or set the env var) before "
            f"any jax device use.", RuntimeWarning, stacklevel=2)
        del _warnings
    del _jax, _live
del _os, _platform

from . import (
    callbacks,
    checkpoint,
    elastic,
    integrity,
    obs,
    parallel,
    runner,
    serving,
    tune,
)
from .obs import (
    health_report,
    metrics_snapshot,
    straggler_report,
    tensor_report,
)
from .basics import (
    cross_rank,
    cross_size,
    init,
    is_initialized,
    local_device_count,
    local_rank,
    local_size,
    mpi_threads_supported,
    num_devices,
    rank,
    shutdown,
    size,
)
from .core.status import (
    ConsensusError,
    HorovodInternalError,
    NonFiniteGradError,
    NotInitializedError,
    RanksAbortedError,
)
from .ops import (
    Compression,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    broadcast,
    broadcast_async,
    poll,
    release,
    spmd,
    synchronize,
)
from .ops.fused_apply import (
    adam as fused_adam,
    momentum as fused_momentum,
    sgd as fused_sgd,
)
from .ops.pallas_attention import flash_attention
from .ops.sparse import IndexedSlices, allreduce_sparse
from .optimizers import DistributedOptimizer, allreduce_gradients, apply_step
from .state_bcast import (
    broadcast_global_variables,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
)

__version__ = "0.1.0"


def __getattr__(name):
    # Framework front-ends are optional (like the torch front-end): flax and
    # haiku are extras, so they must not break `import horovod_tpu` when
    # absent.
    if name in ("flax", "haiku"):
        import importlib

        return importlib.import_module("." + name, __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "__version__",
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "local_device_count", "num_devices", "mpi_threads_supported",
    "allreduce", "allreduce_async", "allgather", "allgather_async",
    "broadcast", "broadcast_async", "poll", "synchronize", "release",
    "Compression", "spmd", "parallel", "callbacks", "checkpoint",
    "elastic", "obs", "tune", "metrics_snapshot", "straggler_report",
    "health_report", "tensor_report",
    "IndexedSlices", "allreduce_sparse", "flash_attention",
    "DistributedOptimizer", "allreduce_gradients", "apply_step",
    "fused_sgd", "fused_momentum", "fused_adam",
    "broadcast_parameters", "broadcast_optimizer_state",
    "broadcast_global_variables", "broadcast_object",
    "HorovodInternalError", "NotInitializedError", "RanksAbortedError",
    "ConsensusError", "NonFiniteGradError", "integrity",
]
