"""Sparse substrate: segment ops, padded CSR/COO builders, 2D partitioning.

JAX has no CSR/CSC (BCOO only), no EmbeddingBag, and no native scatter-based
message passing. Per the project brief these are implemented here from
``jnp.take`` + ``jax.ops.segment_sum``-family primitives and are first-class
parts of the system (used by repro.core, repro.models.gnn, repro.models.recsys).
"""
from repro.sparse.csr import (
    PaddedCSR,
    coo_to_padded_csr,
    dedupe_coo_sum,
    max_row_nnz,
    row_ptr_from_sorted,
    sort_coo,
    window_depth,
)
from repro.sparse.ops import (
    coo_sddmm,
    coo_spmm,
    lex_searchsorted,
    searchsorted_in_window,
    segment_argmax,
    segment_max_with_payload,
    segment_softmax,
    sorted_segment_max_with_payload,
    x64_available,
)
from repro.sparse.partition import (
    Partition2D,
    Partition2DBatched,
    block_occupancy,
    partition_coo_2d,
    partition_coo_2d_batched,
    plan_block_cap,
)

__all__ = [
    "segment_argmax",
    "segment_max_with_payload",
    "segment_softmax",
    "sorted_segment_max_with_payload",
    "coo_spmm",
    "coo_sddmm",
    "lex_searchsorted",
    "searchsorted_in_window",
    "x64_available",
    "PaddedCSR",
    "coo_to_padded_csr",
    "dedupe_coo_sum",
    "max_row_nnz",
    "row_ptr_from_sorted",
    "sort_coo",
    "window_depth",
    "Partition2D",
    "Partition2DBatched",
    "block_occupancy",
    "partition_coo_2d",
    "partition_coo_2d_batched",
    "plan_block_cap",
]
