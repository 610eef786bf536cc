"""The benchmark's own copy of the RadiX-net topology, inputs and reference.

Copied from the program's ``repro.data.radixnet`` so that a later change
to the program cannot move the yardstick; ``chipbench/tests`` checks the
two agree bit for bit. Nothing here imports the program.

Topology (GraphChallenge, arXiv 2004.01181; RadiX-net, arXiv 1905.00416):
``n = 32**k * q`` neurons, every neuron has exactly 32 inbound edges of
weight 1/16, and layer ``l`` uses phase ``l mod num_phases``. The
reference is plain ReLU with the configuration's bias and no YMAX clamp,
the semantics the program serves.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np

FAN_IN = 32
WEIGHT_VALUE = 1.0 / 16.0  # exact in binary floating point


def _factor(neurons: int) -> tuple[int, int]:
    """``neurons = 32**k * q`` with q a power of two in [1, 32)."""
    if neurons < FAN_IN or neurons & (neurons - 1):
        raise ValueError(
            f"RadiX-net sizes must be powers of two >= {FAN_IN}; got {neurons}"
        )
    k, rest = 0, neurons
    while rest % FAN_IN == 0:
        k += 1
        rest //= FAN_IN
    return k, rest


def num_phases(neurons: int) -> int:
    k, q = _factor(neurons)
    return k + (1 if q > 1 else 0)


def radixnet_connectivity(neurons: int, layer: int) -> np.ndarray:
    """The (neurons, 32) int32 column indices of layer ``layer``."""
    k, q = _factor(neurons)
    phase = layer % num_phases(neurons)
    r = np.arange(neurons, dtype=np.int64)[:, None]
    if phase < k:
        stride = FAN_IN**phase
        digit = (r // stride) % FAN_IN
        base = r - digit * stride
        cols = base + np.arange(FAN_IN, dtype=np.int64)[None, :] * stride
    else:
        stride = FAN_IN**k
        g = FAN_IN // q
        digit = (r // stride) % q
        base = r - digit * stride - r % g
        hi = np.arange(q, dtype=np.int64)[:, None] * stride
        lo = np.arange(g, dtype=np.int64)[None, :]
        cols = base + (hi + lo).reshape(1, FAN_IN)
    return cols.astype(np.int32)


def radixnet_input_panel(
    neurons: int, n_inputs: int, *, density: float = 0.3, seed: int = 0
) -> np.ndarray:
    """Seeded {0, 1} float32 inputs, shape (neurons, n_inputs): one input
    per column, a pure function of (neurons, n_inputs, density, seed)."""
    rng = np.random.Generator(
        np.random.Philox(key=seed, counter=[0, 0, neurons, n_inputs])
    )
    panel = rng.random((neurons, n_inputs), dtype=np.float32) < density
    return panel.astype(np.float32)


def _conn_matrix(conn: np.ndarray):
    import scipy.sparse as sp

    n, fan_in = conn.shape
    return sp.csr_matrix(
        (
            np.full(n * fan_in, WEIGHT_VALUE, np.float32),
            np.asarray(conn, np.int64).reshape(-1),
            np.arange(0, n * fan_in + 1, fan_in),
        ),
        shape=(n, n),
    )


def _bf16(y: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return y.astype(ml_dtypes.bfloat16).astype(np.float32)


def round_operands(y: np.ndarray, operands: str) -> np.ndarray:
    """Activations as a matrix unit takes them at ``operands``.

    ``"high"`` is the three-pass product: each operand split into a high
    and a low bfloat16 part, the low-by-low product dropped. The weights
    (1/16) are exact in bfloat16, so that leaves the activation's two
    parts, about 16 significant bits.
    """
    if operands == "float32":
        return y
    if operands == "high":
        hi = _bf16(y)
        return hi + _bf16(y - hi)
    raise ValueError(f"unknown operand precision {operands!r}")


def reference_forward(
    conns: Sequence[np.ndarray],
    biases: Sequence[float],
    y0: np.ndarray,
    *,
    chunk: int = 128,
    operands: str = "float32",
) -> np.ndarray:
    """Per layer ``Y <- max((1/16) * sum_{c in conn[r]} Y[c] + bias, 0)``
    as a scipy CSR product in float32, never densified. Columns are
    independent, so chunks of ``chunk`` inputs run on a thread pool.

    ``operands`` other than ``"float32"`` rounds each layer's input
    activations as ``round_operands`` does before the product and keeps
    the float32 accumulation and epilogue. That is the control the
    comparison must fail; it is never the reference.
    """
    y0 = np.asarray(y0, dtype=np.float32)
    mats: dict[int, object] = {}
    layers = []
    for conn, b in zip(conns, biases):
        if id(conn) not in mats:
            mats[id(conn)] = _conn_matrix(conn)
        layers.append((mats[id(conn)], np.float32(b)))

    def forward(y: np.ndarray) -> np.ndarray:
        for w, b in layers:
            y = round_operands(y, operands)
            y = np.maximum(w @ y + b, np.float32(0.0))
        return y

    starts = range(0, y0.shape[1], chunk)
    if len(starts) <= 1:
        return forward(y0)
    with ThreadPoolExecutor(min(len(starts), os.cpu_count() or 1)) as pool:
        parts = pool.map(forward, [y0[:, s : s + chunk] for s in starts])
        return np.concatenate(list(parts), axis=1)


def reference_categories(y_final: np.ndarray) -> np.ndarray:
    """Indices of inputs (columns) with any positive final activation."""
    return np.flatnonzero(np.asarray(y_final).max(axis=0) > 0).astype(np.int64)


def stack_reference(
    neurons: int,
    layers: int,
    bias: float,
    y0: np.ndarray,
    *,
    chunk: int = 128,
    operands: str = "float32",
) -> np.ndarray:
    """Final activations of the ``neurons x layers`` stack over ``y0``."""
    phases = num_phases(neurons)
    phase_conns = [radixnet_connectivity(neurons, p) for p in range(phases)]
    conns = [phase_conns[l % phases] for l in range(layers)]
    return reference_forward(
        conns, [bias] * layers, y0, chunk=chunk, operands=operands
    )
