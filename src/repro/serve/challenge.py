"""GraphChallenge-shaped streaming inference driver.

Pushes a seeded sparse input set through :class:`SparseDNNEngine` in
width-classed panels — the serving shape of the Sparse DNN GraphChallenge
(arXiv 2004.01181): a ``neurons × layers`` RadiX-net topology
(`repro.data.radixnet`), a {0, 1} input panel with the challenge's 60 000
inputs as columns, and the official rate metric

    edges × inputs / second,   edges = layers · neurons · 32

reported per run. Every panel goes through the engine's normal
submit/step path, so runs exercise exactly what production serving
exercises: plan-cache width classes, the degradation ladder, layered /
sharded routing — a mesh makes this the
"sharded engine" leg of the conformance suite.

The driver never materialises the full output set: each step's panel is
reduced to its per-column activity mask on the spot, and the run's
answer is the challenge category set (indices of inputs with any
positive final activation), bit-comparable against
``repro.data.radixnet.radixnet_reference``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import radixnet as rx
from repro.serve.engine import SparseDNNEngine


@dataclasses.dataclass(frozen=True)
class ChallengeResult:
    """One challenge run's scorecard."""

    spec: rx.RadixNetSpec
    n_inputs: int
    categories: np.ndarray  # ground-truth-comparable answer set
    seconds: float  # timed serving loop (post-warmup)
    edge_inputs_per_sec: float  # the official challenge metric
    steps: int  # engine steps dispatched
    served: int  # input columns served (== n_inputs)
    routes: tuple[str, ...]  # distinct plan routes seen, in order
    levels: tuple[str, ...]  # distinct ladder levels seen, in order
    width_classes: tuple[int, ...]  # distinct padded widths seen
    grid_steps: int  # summed kernel grid-step bill
    warmup_seconds: float  # the untimed warmup panel: plan build + compile
    panels: tuple[dict, ...]  # each timed panel's plan stats (route, level, ...)
    engine: SparseDNNEngine = dataclasses.field(repr=False, compare=False)

    @property
    def edges(self) -> int:
        return self.spec.edges


def _ordered_unique(values) -> tuple:
    seen: dict[Any, None] = {}
    for v in values:
        seen.setdefault(v)
    return tuple(seen)


def run_challenge(
    spec: rx.RadixNetSpec,
    *,
    n_inputs: int = 60000,
    panel_width: int = 512,
    batch_align: int = 32,
    density: float = 0.3,
    seed: int = 0,
    mesh: Any = None,
    engine: SparseDNNEngine | None = None,
    warmup: bool = True,
    block_size: int = 16,
    tuning_table: Any = None,
) -> ChallengeResult:
    """Stream ``n_inputs`` seeded inputs through the engine, panelwise.

    ``engine``: pass a prebuilt engine (e.g. with a fault injector or a
    shared plan cache) — it must serve the spec's topology; by default
    the driver builds one from :func:`repro.data.radixnet
    .radixnet_weights` with the given ``mesh``.
    ``warmup`` runs one untimed panel of the same width class first so
    the metric bills steady-state serving, not plan compilation; its
    wall time (plan build, compile and one panel) is ``warmup_seconds``.
    ``tuning_table`` threads straight into the default engine
    (``repro.tune``): a table hit on this spec's fingerprint retunes
    every panel's plan.
    """
    if engine is None:
        weights, biases = rx.radixnet_weights(spec, block_size=block_size)
        engine = SparseDNNEngine(
            weights,
            biases,
            batch_align=batch_align,
            mesh=mesh,
            tuning_table=tuning_table,
        )
    panel = jnp.asarray(
        rx.radixnet_input_panel(
            spec.neurons, n_inputs, density=density, seed=seed
        )
    )
    t0 = time.perf_counter()
    if warmup:
        engine.submit(panel[:, : min(panel_width, n_inputs)])
        out, _ = engine.step(pad_to=panel_width)
        if out is not None:
            jax.block_until_ready(out)
    warmup_seconds = time.perf_counter() - t0

    active = np.zeros((n_inputs,), dtype=bool)
    step_stats: list[dict] = []
    steps = served = grid_steps = 0
    t0 = time.perf_counter()
    for start in range(0, n_inputs, panel_width):
        chunk = panel[:, start : start + panel_width]
        engine.submit(chunk)
        out, stats = engine.step(pad_to=panel_width)
        if out is None or stats["failed"]:
            raise RuntimeError(
                f"challenge panel at column {start} failed: "
                f"{stats.get('error', 'no output')}"
            )
        width = chunk.shape[1]
        active[start : start + width] = np.asarray(
            (out[:, :width] > 0).any(axis=0)
        )
        steps += 1
        served += stats["batch"]
        grid_steps += stats["grid_steps"]
        step_stats.append(stats)
    jax.block_until_ready(out)
    seconds = time.perf_counter() - t0

    return ChallengeResult(
        spec=spec,
        n_inputs=n_inputs,
        categories=np.flatnonzero(active).astype(np.int64),
        seconds=seconds,
        edge_inputs_per_sec=spec.edges * n_inputs / max(seconds, 1e-9),
        steps=steps,
        served=served,
        routes=_ordered_unique(
            s["plan"]["route"] for s in step_stats if s["plan"]
        ),
        levels=_ordered_unique(
            s["plan"]["level"] for s in step_stats if s["plan"]
        ),
        width_classes=_ordered_unique(
            s["padded_batch"] for s in step_stats
        ),
        grid_steps=grid_steps,
        warmup_seconds=warmup_seconds,
        panels=tuple(s["plan"] for s in step_stats),
        engine=engine,
    )
