"""Pallas TPU kernel for the decision plane's single-pass decision.

The paper's §5.2 single-pass CPU kernels become, on TPU, a fused
HBM-streaming Pallas kernel with explicit BlockSpec VMEM tiling:

* fused_kernel — penalties → temperature → streaming top-K + masses →
                 truncation-first filter → Gumbel-max draw, in one read of
                 the logits (the ``fused`` sampler backend, DESIGN.md §14)

``ops.py`` holds the public wrapper (padding + interpret switch);
``ref.py`` the pure-jnp oracle the kernel is tested against and the tile
helpers both share.
"""
