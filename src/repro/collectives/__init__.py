"""Pluggable bucket-fused collective engine for gradient synchronization.

Layout:
  registry.py    — register_backend / get_backend
  backends.py    — psum | ring | optinc | cascade implementations with
                   per-backend wire-byte accounting (bytes_on_wire)
  bucketizer.py  — pytree <-> fixed-size fused f32 buckets
  engine.py      — SyncConfig + sync_gradients (the train-step entry)

``repro.core.collective`` re-exports this surface for backwards
compatibility with the pre-refactor import path.
"""
from .backends import (CascadeBackend, OptincBackend, PsumBackend,
                       RingBackend, _ring_allreduce_flat)
from .bucketizer import (DEFAULT_BUCKET_BYTES, BucketLayout, bucketize,
                         expected_buckets, make_layout, tree_bucketize,
                         tree_unbucketize, unbucketize)
from .engine import (SyncConfig, is_packed_residuals, pack_residuals,
                     residual_size, sync_gradients, unpack_residuals)
from .registry import available_backends, get_backend, register_backend

__all__ = [
    "SyncConfig", "sync_gradients", "residual_size",
    "pack_residuals", "unpack_residuals", "is_packed_residuals",
    "register_backend", "get_backend", "available_backends",
    "PsumBackend", "RingBackend", "OptincBackend", "CascadeBackend",
    "BucketLayout", "make_layout", "bucketize", "unbucketize",
    "tree_bucketize", "tree_unbucketize", "expected_buckets",
    "DEFAULT_BUCKET_BYTES",
]
