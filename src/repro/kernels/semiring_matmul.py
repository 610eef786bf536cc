"""Dense semiring matmul Pallas TPU kernel (paper §II-D / §III).

Computes ``C = A ⊕.⊗ B`` (+ optional fused max-plus bias/ReLU epilogue)
with explicit VMEM tiling:

* grid = (m/bm, n/bn, k/bk); the (i, j) output tile lives in a VMEM f32
  scratch accumulator across the k-steps (classic revisiting pattern).
* ``plus_times`` uses the MXU (``jnp.dot`` with f32 accumulation).
* every other registry semiring runs its tile product on the VPU; the
  (bm, bk, bn) broadcast is chunked along k (``semirings.K_CHUNK``) so
  the working set stays ≪ VMEM:  bm·bn·4  +  bm·chunk·bn·4 bytes.

Semiring dispatch (⊗/⊕ ops, accumulator init, annihilator fill) is
derived from ``core/semiring.py``'s registry by
``repro.kernels.semirings`` — the whole registry is supported, and
adding a semiring there is a one-place change.

TARGET is TPU; on CPU this file is exercised via ``interpret=True``
(see ``repro.kernels.ops``), checked against ``repro.kernels.ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import DEFAULT_BLOCK_N
from repro.kernels.semirings import accumulate_tile, kernel_semiring

Array = jax.Array


def _kernel(
    a_ref,
    b_ref,
    bias_ref,
    o_ref,
    acc_ref,
    *,
    semiring_name: str,
    k_steps: int,
    fuse_bias_relu: bool,
):
    spec = kernel_semiring(semiring_name)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        # ⊕-identity init (0 for plus_times, ±inf for the tropical
        # family, 0 for the boolean encodings, -inf for log_plus)
        acc_ref[...] = jnp.full_like(acc_ref, spec.init)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] = accumulate_tile(spec, a, b, acc_ref[...])

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_bias_relu:
            # max-plus pass of the paper fused in: max(acc + bias, 0).
            acc = jnp.maximum(acc + bias_ref[...].astype(jnp.float32), 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def semiring_matmul(
    a: Array,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
    block_m: int = DEFAULT_BLOCK_N,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
    out_dtype=None,
) -> Array:
    """C = A ⊕.⊗ B with optional fused ``max(C + bias, 0)`` epilogue.

    a: (m, k); b: (k, n); bias: (m,) broadcast along n (paper's B[k]).
    m/k/n must divide the block sizes (wrappers in ``ops.py`` pad).
    Any registry semiring; unknown names raise ``KeyError`` at trace
    time via ``kernels.semirings``.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k),
        (block_m, block_n, block_k),
    )
    kernel_semiring(semiring_name)  # fail fast on unknown semirings
    if fuse_bias_relu and bias is None:
        raise ValueError("fuse_bias_relu requires bias")
    if bias is None:
        bias = jnp.zeros((m,), jnp.float32)
    bias2d = bias[:, None]  # (m, 1) so the tile is (block_m, 1)

    k_steps = k // block_k
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    kernel = functools.partial(
        _kernel,
        semiring_name=semiring_name,
        k_steps=k_steps,
        fuse_bias_relu=fuse_bias_relu,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b, bias2d)
