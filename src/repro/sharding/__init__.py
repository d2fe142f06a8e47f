"""Mesh partitioning: logical-axis rules, pipeline parallelism, and the
one ``shard_map`` entry point every in-repo SPMD program goes through.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = False):
    """``jax.shard_map`` with the repo's replication-check default.

    ``check_replication=False`` maps to ``check_vma=False``: our staged
    functions produce replicated outputs via explicit psums, which the
    checker cannot always prove.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


__all__ = ["shard_map"]
