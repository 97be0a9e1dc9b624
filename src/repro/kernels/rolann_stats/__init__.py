from repro.kernels.rolann_stats.ops import (  # noqa: F401
    rolann_fused_chunk,
    rolann_fused_chunk_batched,
    rolann_stats,
    rolann_stats_acc,
    rolann_stats_acc_batched,
    rolann_stats_batched,
    rolann_stats_ref,
)
