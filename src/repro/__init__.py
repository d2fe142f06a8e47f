"""repro — production JAX framework reproducing & extending the TNN-7nm paper.

Paper: "A Custom 7nm CMOS Standard Cell Library for Implementing TNN-based
Neuromorphic Processors" (Nair, Vellaisamy, Bhasuthkar, Shen — CMU NCAL, 2020).

Public API surface:
    repro.core      — the paper's contribution: TNN columns/layers, STDP, WTA,
                      and the macro-level PPA hardware model.
    repro.kernels   — Pallas TPU kernels for the TNN hot loops; the
                      ``impl="pallas"`` backend (Mosaic on TPU, the
                      bit-exact interpreter on CPU — DESIGN.md §8).
    repro.models    — LM-family architecture substrate (10 assigned archs).
    repro.configs   — named architecture configs (``get_config(name)``).
    repro.sharding  — mesh partitioning rules + the one shard_map entry.
    repro.train     — optimizers, train-step builder, trainer loop.
    repro.serve     — KV-cache LM engine and the slot-batched TNNEngine.
    repro.launch    — production mesh, dry-run, train/serve drivers.

Usage — run the paper's 2-layer prototype through the fused kernel path::

    import jax
    from repro.core import (encode_images, init_network, network_forward,
                            prototype_config, with_impl)

    cfg = with_impl(prototype_config(), "pallas")   # fused Pallas backend
    params = init_network(jax.random.PRNGKey(0), cfg)
    z = network_forward(encode_images(images, cfg), params, cfg)[-1]

The raw kernel entry points (padding + interpret flag handled for you) live in
``repro.kernels``: ``column_forward``, ``wta``, ``stdp_update``, and the
layer-level ``layer_forward_fused`` / ``layer_stdp_fused`` — see
``repro/kernels/ops.py`` for the padding semantics and a full example.
"""

__version__ = "1.1.0"
