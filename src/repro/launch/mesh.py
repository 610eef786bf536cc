"""Production meshes (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import, and everything else (tests, benches) must keep seeing 1 device.

Axis semantics:
  pod   — outer pure-DP axis; only the per-step gradient reduction
          crosses it (DCI traffic), never TP/EP collectives.
  data  — FSDP: batch + parameter/optimizer sharding (intra-pod ICI).
  model — TP/EP: heads, mlp, experts, vocab shards (intra-pod ICI).

Every mesh here has **Auto** axes: ``jax.make_mesh`` defaults to
Explicit axes, under which ``with_sharding_constraint`` rejects specs
naming the mesh's axes and jit-level sharding propagation changes. The
code in this repo places data with ``NamedSharding`` /
``with_sharding_constraint`` and ``shard_map``, which is the Auto-axis
contract.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(
    data: int | None = None, model: int = 1
) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def make_row_blocks_mesh(shards: int | None = None) -> jax.sharding.Mesh:
    """1-D mesh over the ``row_blocks`` axis — the sparse-stack shard
    axis (``repro.sparse.partition`` / ``repro.plan.ShardedStackPlan``).

    ``shards=None`` uses every visible device. On CPU hosts set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax import to get N fake devices (the sharding tests and
    ``examples/serve_stream.py --shards N`` do exactly this).
    """
    n = len(jax.devices())
    shards = n if shards is None else shards
    return _auto_mesh((shards,), ("row_blocks",))
