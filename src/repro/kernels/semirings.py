"""Registry-derived semiring dispatch for the Pallas kernels.

The kernels compute in f32 VMEM tiles, so every registry semiring
(``repro.core.semiring.REGISTRY``) is lowered here to an f32-space
:class:`KernelSemiring`: the per-block ⊗-product, the binary ⊕ that
merges chunk/block partials into the accumulator, the axis form of ⊕
for the chunked VPU broadcast, and the accumulator init — which is the
⊕-identity AND the ⊗-annihilator (one value, by the semiring axioms),
so it doubles as the fill for k-padding and empty-row splices.

This module is the ONE place kernel semantics are derived from the
`Semiring` objects: adding a semiring to ``core/semiring.py`` whose
⊕/⊗ are drawn from the op translation tables below makes it available
to the dense, ELL, and block-CSR kernels with no kernel edits
(previously each kernel carried its own ``_VPU_SEMIRINGS`` copy of
⊕/⊗/identity).

Boolean semirings (lor_land, xor_and) run in the {0.0, 1.0} ⊂ f32
encoding: ⊗ canonicalises both operands through ``!= 0`` so arbitrary
float inputs behave like their truth values, ⊕ stays exact on {0, 1}
(max for ∨, sum-mod-2 for ⊻). The kernel output is the f32 encoding of
the boolean result — compare against ``Semiring.matmul`` after an
``astype(float32)`` of its bool output.

``plus_times`` is the only MXU semiring (``jnp.dot``); everything else
takes the chunked VPU broadcast (``vpu_tile_product``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import semiring as _core

Array = jax.Array

# k-slab for the chunked VPU tile product: the (bm, chunk, bn) broadcast
# working set stays ≪ VMEM at 8 sublanes.
K_CHUNK = 8


@dataclasses.dataclass(frozen=True)
class KernelSemiring:
    """One registry semiring lowered to f32 kernel ops.

    ``init`` is the ⊕-identity == ⊗-annihilator in the f32 encoding:
    the accumulator init value, the k-padding fill, and the empty-row
    splice value, all at once.
    """

    name: str
    mul: Callable[[Array, Array], Array]  # elementwise ⊗, f32-space
    add: Callable[[Array, Array], Array]  # binary ⊕ (accumulator merge)
    add_reduce: Callable[[Array, int], Array]  # ⊕ along one axis
    init: float  # ⊕-identity / ⊗-annihilator
    mxu: bool  # True only for plus_times (jnp.dot path)

    def __hash__(self) -> int:
        return hash(self.name)


# --- f32 encodings of the boolean ops ------------------------------------


def _f32_and(a: Array, b: Array) -> Array:
    return jnp.logical_and(a != 0, b != 0).astype(jnp.float32)


def _f32_or(a: Array, b: Array) -> Array:
    # exact ∨ on the {0, 1} encoding (⊗ above canonicalises inputs)
    return jnp.maximum(a, b)


def _f32_xor(a: Array, b: Array) -> Array:
    return jnp.logical_xor(a != 0, b != 0).astype(jnp.float32)


def _f32_xor_reduce(x: Array, axis: int) -> Array:
    # parity: sums of {0, 1} f32 are exact far beyond any tile width
    return jnp.mod(jnp.sum(x, axis=axis), 2.0)


def _logaddexp_reduce(x: Array, axis: int) -> Array:
    return jax.nn.logsumexp(x, axis=axis)


# --- op translation: core-registry callables → f32 kernel ops ------------
# Keyed by the IDENTITY of the ops the `Semiring` objects carry, so the
# lowering reads ⊕/⊗/zero straight off the registry entry.

_MUL_F32: dict[Callable, Callable[[Array, Array], Array]] = {
    jnp.multiply: jnp.multiply,
    jnp.add: jnp.add,
    jnp.minimum: jnp.minimum,
    jnp.maximum: jnp.maximum,
    jnp.logical_and: _f32_and,
}

# ⊕ → (binary merge, axis reduce — called as fn(x, axis))
_ADD_F32: dict[Callable, tuple[Callable, Callable]] = {
    jnp.add: (jnp.add, jnp.sum),
    jnp.maximum: (jnp.maximum, jnp.max),
    jnp.minimum: (jnp.minimum, jnp.min),
    jnp.logical_or: (_f32_or, jnp.max),
    jnp.logical_xor: (_f32_xor, _f32_xor_reduce),
    jnp.logaddexp: (jnp.logaddexp, _logaddexp_reduce),
}


def _lower(sr: _core.Semiring) -> KernelSemiring:
    try:
        mul = _MUL_F32[sr.mul]
        add, add_reduce = _ADD_F32[sr.add]
    except KeyError as e:
        raise NotImplementedError(
            f"semiring {sr.name!r} uses ops with no f32 kernel lowering; "
            f"register them in repro.kernels.semirings"
        ) from e
    return KernelSemiring(
        name=sr.name,
        mul=mul,
        add=add,
        add_reduce=add_reduce,
        init=float(sr.zero),
        mxu=(sr.name == "plus_times"),
    )


@functools.cache
def kernel_semiring(name: str) -> KernelSemiring:
    """The f32 kernel lowering of registry semiring ``name``.

    Raises ``KeyError`` for names not in the core registry — the kernels
    support exactly what ``core/semiring.py`` defines, by construction.
    """
    return _lower(_core.get_semiring(name))


def kernel_zero(name: str) -> float:
    """⊕-identity / ⊗-annihilator fill for ``name`` (f32 encoding)."""
    return kernel_semiring(name).init


def supported() -> tuple[str, ...]:
    """Every semiring the kernels speak — the whole core registry."""
    return tuple(sorted(_core.REGISTRY))


def vpu_tile_product(
    spec: KernelSemiring, a: Array, b: Array, acc: Array
) -> Array:
    """acc ⊕= A_tile ⊗-contract B_tile on the VPU, k chunked by K_CHUNK.

    a: (bm, bk); b: (bk, bn); acc: (bm, bn) — bk must divide K_CHUNK.
    Each chunk broadcasts to (bm, chunk, bn), ⊕-reduces its own k slab,
    then ⊕-merges into the accumulator; both steps use the semiring's
    exact f32 ops, so any k association gives the same result for the
    order-independent monoids (max/min/or/xor).
    """
    bk = a.shape[1]
    n_chunks = bk // K_CHUNK

    def body(c, acc):
        a_c = jax.lax.dynamic_slice_in_dim(a, c * K_CHUNK, K_CHUNK, axis=1)
        b_c = jax.lax.dynamic_slice_in_dim(b, c * K_CHUNK, K_CHUNK, axis=0)
        prod = spec.mul(a_c[:, :, None], b_c[None, :, :])  # (bm, chunk, bn)
        return spec.add(acc, spec.add_reduce(prod, 1))

    return jax.lax.fori_loop(0, n_chunks, body, acc)


def mxu_dot(a: Array, b: Array) -> Array:
    """``a @ b`` accumulated in f32. Two f32 operands multiply at full
    f32 precision: Mosaic's default rounds them to bf16 on the MXU,
    which on a v5e turned the 1040 RadiX-net 16384×120 categories of
    2048 inputs into 1090. A bf16 operand (bf16 activation panels)
    keeps the one-pass product."""
    full = a.dtype == jnp.float32 and b.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if full else jax.lax.Precision.DEFAULT
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=precision)


def accumulate_tile(
    spec: KernelSemiring, a: Array, b: Array, acc: Array
) -> Array:
    """One kernel accumulation step: MXU dot for plus_times, chunked VPU
    broadcast for everything else. The shared inner reduce of all three
    kernels (dense / ELL / block-CSR)."""
    if spec.mxu:
        return acc + mxu_dot(a, b)
    return vpu_tile_product(spec, a, b, acc)
