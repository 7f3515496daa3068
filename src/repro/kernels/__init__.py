"""Pallas TPU kernels for the compute hot spots (compiled for the TPU;
interpret mode only when the caller passes ``interpret=True``):

- ``lstm_cell``        fused gate matmul + elementwise (the paper's model)
- ``flash_attention``  GQA online-softmax attention, causal/sliding-window
- ``rmsnorm``          fused row norm
- ``ternary``          TernGrad 2-bit gradient pack/unpack (paper §III fix)

Each is exported from ``ops`` and has a pure-jnp oracle in ``ref``.
"""
from repro.kernels import ops, ref  # noqa: F401
