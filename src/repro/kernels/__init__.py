"""Pallas TPU kernels for the paper's compute hot-spots (+ jnp oracles).

Layout per the repo convention: ``<name>.py`` holds the ``pl.pallas_call`` +
BlockSpec kernel, ``ops.py`` the jit'd wrappers + the SCAN/MERGE backend
registries, ``ref.py`` the pure-jnp oracles used by the allclose sweeps in
tests/.
"""
from .delta_splice import (
    gather_splice,
    merge_ranks,
    searchsorted_pairs,
    sparse_splice_plan,
    splice_payload,
)
from .ops import (
    bucket_kselect_op,
    fused_scan_merge_op,
    get_merge_backend,
    get_scan_backend,
    merge_backend_names,
    merge_topk_lists_op,
    multi_merge_lists_op,
    pairwise_dist_op,
    register_merge_backend,
    register_scan_backend,
    scan_backend_names,
    topk_select_op,
    tree_merge_lists,
)
from .ref import (
    bucket_kselect_ref,
    merge_topk_lists_ref,
    pairwise_dist_ref,
    topk_select_ref,
)
from .refine import MIXED_WIDEN, mixed_prune_keep

__all__ = [
    "bucket_kselect_op",
    "fused_scan_merge_op",
    "merge_topk_lists_op",
    "multi_merge_lists_op",
    "pairwise_dist_op",
    "topk_select_op",
    "MIXED_WIDEN",
    "mixed_prune_keep",
    "bucket_kselect_ref",
    "merge_topk_lists_ref",
    "pairwise_dist_ref",
    "topk_select_ref",
    "get_scan_backend",
    "register_scan_backend",
    "scan_backend_names",
    "get_merge_backend",
    "register_merge_backend",
    "merge_backend_names",
    "tree_merge_lists",
    "merge_ranks",
    "searchsorted_pairs",
    "splice_payload",
    "sparse_splice_plan",
    "gather_splice",
]
