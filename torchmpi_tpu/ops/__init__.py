"""Pallas TPU kernels: custom collectives over ICI remote DMA, plus hot-op
compute kernels.

``ring`` is the analog of the reference's hand-tuned chunked/pipelined
collective algorithms (SURVEY.md §3 C4: ring/tree over MPI_Isend/Irecv +
CUDA IPC).  On TPU the point-to-point transport is inter-chip RDMA issued
from Pallas kernels; the ring algorithm is the same one the reference
pipelined over MPI p2p.  ``flash`` is the blocked-attention compute kernel
serving the beyond-reference long-context stack; ``xent`` the fused
linear + cross-entropy head; ``moe`` the dropless expert layer's row
movement over the routed rows only.
"""

from . import ring  # noqa: F401  (registers the "pallas" backend)
from .flash import flash_attention  # noqa: F401
from .xent import fused_linear_cross_entropy  # noqa: F401
