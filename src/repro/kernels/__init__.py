"""Pallas TPU kernels for the engine's compute hot-spots.

``fused_relax_reduce`` — the per-round relax phase: the frontier
gather + semiring relax + active mask fusion feeding a blocked segment
reduction with two-level grid-cell skipping.
``rhizome_segment_reduce`` — the standalone blocked semiring segment
reduction (the unfused inbox collapse, kept as the reduce-only fallback).
``ops`` holds the jit'd wrappers, ``ref`` the pure-jnp oracles.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
