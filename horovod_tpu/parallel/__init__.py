"""Mesh construction and multi-axis parallelism utilities (SURVEY §2.10)."""

from .hierarchical import (
    hierarchical_allgather,
    hierarchical_allreduce,
    hierarchical_grad_allreduce,
)
from .ring_attention import (
    dense_attention,
    ring_attention,
    ulysses_attention,
)
from .mesh import (
    DATA_AXIS,
    DCN_AXIS,
    ICI_AXIS,
    data_parallel_mesh,
    hierarchical_mesh,
    local_mesh,
)
from .step import data_parallel_step

__all__ = [
    "DATA_AXIS", "DCN_AXIS", "ICI_AXIS",
    "data_parallel_mesh", "hierarchical_mesh", "local_mesh",
    "data_parallel_step",
    "hierarchical_allreduce", "hierarchical_allgather",
    "hierarchical_grad_allreduce",
    "ring_attention", "ulysses_attention", "dense_attention",
]
