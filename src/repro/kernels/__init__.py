"""Pallas TPU kernels for the compute hot-spots, with pure-jnp oracles.
Public API: repro.kernels.ops (padding + dispatch wrappers)."""

# Scoped VMEM each selection kernel may use: v5e's default, passed to Mosaic
# explicitly so that ops' capacity estimate and the compiler share one number.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
