"""edge_relax — the paper's memory-driven execution model as a kernel.

The paper's central claim is that dynamic graph processing should be
*memory-driven*: computation is carried to the memory that owns the data
(the compute cell holding a vertex block), instead of data being hauled to
a central processor.  This package is that claim expressed at the kernel
level, for the engine's hot loop (one relaxation sweep of one cell):

* the cell's **vertex block is the operand the stream is relaxed
  against** — the paper's "computation moves to where the vertex data
  lives" (and the Dalorex/Rhizomes argument that fusing
  gather→combine→scatter at the data is where memory-bound graph
  workloads win).  Neither backend keeps it resident in VMEM: Mosaic
  lowers no gather from a vertex block held there, so on a TPU the
  per-edge reads of it are one XLA gather from HBM ahead of the Pallas
  kernel, which only emits and combines;
* the **edge stream is the moving operand** — it arrives in the graph's
  destination-sorted blocked-CSR layout (``ShardedGraph.with_csr``), so
  each destination's messages form one contiguous run: the XLA path
  combines them with a segmented scan read out at each run's end (no
  scatter), the Pallas block kernel with a dense-rank one-hot reduction
  per block (shared with ``segment_reduce``; MXU-shaped for the sum
  monoid);
* the result is the cell's **operon traffic**: a combined per-destination
  message table over the flat ``(dst_shard, dst_local)`` key space — row
  *self* is the local inbox, the other rows are the coalesced cross-cell
  mailbox entries of diffuse.py's round exchange.

Layout: kernel.py (Pallas ``pallas_call``; interpret mode off-TPU),
ref.py (shared per-block math + XLA reference paths), ops.py (backend
dispatch + the shared cross-block phase 2).  Both backends are
bitwise-identical by construction — see ops.py.
"""

from .ops import RELAX_BACKENDS, edge_relax

__all__ = ["edge_relax", "RELAX_BACKENDS"]
