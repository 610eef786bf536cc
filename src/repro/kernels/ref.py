"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.semiring import get_semiring
from repro.sparse import ops as sparse_ops
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array


def semiring_matmul_ref(
    a: Array,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
) -> Array:
    sr = get_semiring(semiring_name)
    out = sr.matmul(a.astype(jnp.float32), b.astype(jnp.float32))
    if fuse_bias_relu:
        out = jnp.maximum(out + bias.astype(jnp.float32)[:, None], 0.0)
    return out


def bsr_spmm_ref(
    a: BlockSparseMatrix,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
) -> Array:
    sr = get_semiring(semiring_name)
    out = sparse_ops.bsr_matmul(a.astype(jnp.float32), b.astype(jnp.float32), sr)
    if fuse_bias_relu:
        out = jnp.maximum(out + bias.astype(jnp.float32)[:, None], 0.0)
    return out


def bcsr_spmm_ref(
    a: BlockCSRMatrix,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
) -> Array:
    sr = get_semiring(semiring_name)
    out = sparse_ops.bcsr_matmul(
        a.astype(jnp.float32), b.astype(jnp.float32), sr
    )
    if fuse_bias_relu:
        out = jnp.maximum(out + bias.astype(jnp.float32)[:, None], 0.0)
    return out

