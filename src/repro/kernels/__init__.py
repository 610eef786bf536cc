"""Pallas TPU kernels + jit'd wrappers (``ops``) and jnp oracles (``ref``).

Kernel menu and when dispatch picks which (see ``repro.plan.routes``):

  semiring_matmul — dense ⊕.⊗ with fused bias/ReLU epilogue; the BLAS
      arm and the fallback for weights with no sparse structure.
  bsr_spmm        — ELL-padded BSR × dense. Grid ``(nrb, n_tiles,
      max_blocks_per_row)``: best for *regular* topologies where every
      block-row stores ≈ the same number of blocks.
  bcsr_spmm       — occupancy-exact block-CSR × dense. Grid ``(n_tiles,
      total_nnz_blocks)``: compute and DMA scale with true nnz, the
      right arm for skewed or magnitude-pruned topologies, and the one
      every RadiX-net layer runs (``repro.plan.components``).

``autodiff`` holds the ``jax.custom_vjp`` rules that make the two SpMM
wrappers trainable (sparse-preserving weight cotangents, kernel-
resident backward for the CSR layout); ``ops`` attaches them for the
``plus_times`` semiring. See docs/kernels.md for the full contract.
"""

# The hand-picked column-tile width every kernel defaults to. ONE
# definition so the autotuner (``repro.tune``) overrides it in a single
# place; defined BEFORE the submodule imports so they can pull it from
# the (partially initialised) package during their own import.
DEFAULT_BLOCK_N = 128

# Scalar-prefetch operands live in the TensorCore's 1 MiB SMEM (TPU
# v5e). Mosaic keeps about 1 KiB of its own there; the reserve covers
# it. A kernel whose prefetch operands pass this budget is refused at
# compile time ("Ran out of memory in memory space smem"), so the route
# layer checks ``prefetch_fits`` before it picks a kernel.
SMEM_BYTES = 1024 * 1024
SMEM_RESERVE_BYTES = 16 * 1024


def smem_bytes(shape) -> int:
    """SMEM bytes of one int32 array as Mosaic lays it out: a 1-D array
    pads to a multiple of 1024 words; a higher-rank array pads its last
    two dims to (8, 128) tiles. (Checked against the v5e compiler in
    tests/test_tpu_compile.py.)"""
    if len(shape) == 1:
        return -(-shape[0] // 1024) * 1024 * 4
    lead = 1
    for d in shape[:-2]:
        lead *= d
    return lead * -(-shape[-2] // 8) * 8 * -(-shape[-1] // 128) * 128 * 4


def prefetch_fits(*shapes) -> bool:
    """Do these scalar-prefetch operands fit SMEM together?"""
    used = sum(smem_bytes(s) for s in shapes)
    return used <= SMEM_BYTES - SMEM_RESERVE_BYTES


from repro.kernels import autodiff, ops, ref  # noqa: E402

__all__ = [
    "DEFAULT_BLOCK_N",
    "SMEM_BYTES",
    "autodiff",
    "ops",
    "prefetch_fits",
    "ref",
    "smem_bytes",
]
