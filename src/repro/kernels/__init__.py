"""Pallas TPU kernels for the Zampling hot spots.

``qz_reconstruct`` — materialization-free ``w = Q z`` (fwd + bwd);
``qz_decode`` — the serve matmul straight off the encoded words.  Both
compile for a TPU and run in interpret mode on the CPU, where they are
validated against the jnp paths.  ``ops`` holds the jit'd public
wrappers with the custom VJP and impl dispatch.
"""

from . import ops, qz_reconstruct, ref

__all__ = ["ops", "qz_reconstruct", "ref"]
