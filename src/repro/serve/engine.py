"""Batched serving engines.

``Engine`` — LLM prefill + decode loop with sampling. Owns the decode
cache (GQA KV / MLA latent / SSM state — built by ``Model.init_cache``
per the arch's mixer kinds) and drives jit'd ``prefill`` /
``decode_step`` functions. Requests are served in aligned batches
(continuous batching is a scheduler concern above this layer; the
dry-run cells ``decode_32k``/``long_500k`` lower exactly the
``decode_step`` this engine calls in its loop).

``SparseDNNEngine`` — the paper's workload as a service: batched forward
passes through a deep sparse ReLU MLP (GraphChallenge-style inference).
Requests are feature columns; the engine right-pads each batch to the
kernel tile, dispatches the plan's layered forward (one fused
SpMM + bias + ReLU kernel launch per layer), and reports per-batch
kernel-step accounting so operators can see the nnz-proportional
scaling live.

Two call conventions on ``SparseDNNEngine``:

* **one-shot** — ``infer(y0)``: one aligned right-padded batch per call
  (the original API, now a thin wrapper over the step API);
* **step-level** — ``submit(cols)`` stages feature columns,
  ``step(limit=...)`` dispatches one padded panel over what is staged,
  ``drain()`` steps until the stage is empty. This is the surface
  ``repro.serve.scheduler.ContinuousBatcher`` drives: it decides *what*
  to stage each scheduling tick (admission, priorities, deadlines,
  mid-flight joins) while the engine stays the only component that
  touches kernels. Step stats carry exact grid-step accounting
  (``repro.core.dnn.dnn_grid_steps``) so pad waste is visible as
  hardware-independent kernel steps, not just wall-clock.

Execution is plan-backed (``repro.plan``, `docs/architecture.md`): the
engine fingerprints its (frozen) topology once, and every ``step``
fetches a compiled :class:`repro.plan.StackPlan` from its
:class:`repro.plan.PlanCache` keyed by the padded panel width — route,
layouts, grid-step bill, and the jitted executable are all amortized
across requests. ``step(pad_to=...)`` lets a scheduler quantize panel
widths to a small set of classes so a handful of compiled plans serve
every panel (``ContinuousBatcher(width_classes=...)``).

While ``jax.profiler`` traces, ``step`` and the plan cache open host
spans (``repro.spans``): ``engine.step`` around a dispatching step, and
inside it ``engine.stage``, ``engine.plan`` (``plan.build`` on a miss),
``engine.dispatch`` and ``engine.finite_sync`` — see ``docs/serving.md``,
"Tracing".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import dnn
from repro.models.model import Model
from repro.plan import DegradationLadder, PlanCache, topology_fingerprint
from repro.serve.clock import WALL_CLOCK
from repro.testing import faults as _faults

Array = jax.Array


def cache_nbytes(cache: Any) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))


def sample_token(logits: Array, key: Array, temperature: float = 0.0) -> Array:
    """Greedy (T=0) or temperature sampling over (B, V) logits."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(
        jnp.int32
    )


@dataclasses.dataclass
class Engine:
    model: Model
    params: Any
    batch_size: int
    cache_len: int
    temperature: float = 0.0
    seed: int = 0

    def __post_init__(self):
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)
        self._key = jax.random.key(self.seed)

    def generate(
        self, prompts: Array, max_new_tokens: int
    ) -> tuple[Array, dict]:
        """prompts: (B, S_prompt) int32 (right-aligned, no padding support
        needed for the aligned-batch benchmark path). Returns (B, new)."""
        b, s = prompts.shape
        assert b == self.batch_size
        cache = self.model.init_cache(b, self.cache_len)
        logits, cache = self._prefill(self.params, prompts, cache)
        self._key, k = jax.random.split(self._key)
        tok = sample_token(logits[:, -1], k, self.temperature)
        out = [tok]
        for i in range(max_new_tokens - 1):
            pos = jnp.asarray(s + i, jnp.int32)
            logits, cache = self._decode(self.params, tok, cache, pos)
            self._key, k = jax.random.split(self._key)
            tok = sample_token(logits, k, self.temperature)
            out.append(tok)
        tokens = jnp.stack(out, axis=1)
        stats = {
            "prompt_tokens": b * s,
            "generated_tokens": b * max_new_tokens,
            "cache_bytes": cache_nbytes(cache),
        }
        return tokens, stats


@dataclasses.dataclass
class SparseDNNEngine:
    """Serve batched inference through the paper's deep sparse MLP.

    ``weights``/``biases``: the L-layer stack (dense, BSR, or block-CSR
    per layer — ``repro.core.dnn`` dispatch rules apply). ``infer``
    accepts (m, batch) activation panels of any batch size; batches are
    padded to ``batch_align`` so the jit cache stays warm across request
    sizes. ``differentiable=True`` guarantees the served forward is
    ``jax.grad``-compatible (custom-VJP kernels on the caller's
    layouts; dense layers in the XLA form). ``mesh=``
    serves the stack mesh-sharded (``repro.plan.ShardedStackPlan``):
    same outputs, per-shard grid-step accounting in the step stats.
    """

    weights: Sequence[dnn.Weight]
    biases: Sequence[Array]
    batch_align: int = 64
    # Differentiable serving (gradient-based attribution, fine-tuning
    # against served traffic): plans keep the caller's layouts so the
    # custom-VJP kernels' cotangents line up.
    differentiable: bool = False
    # Compiled-plan cache (one per engine unless shared explicitly):
    # holds one StackPlan per padded panel width seen; size it to the
    # number of width classes the scheduler quantizes to.
    plan_cache: PlanCache | None = None
    # Mesh-sharded serving: partition every sparse layer's block-CSR
    # segment across the mesh's row_blocks axes and serve through
    # repro.plan.ShardedStackPlan (shard-local kernels + psum between
    # layers). Outputs match the single-device engine; step stats grow
    # per-shard grid-step accounting.
    mesh: Any = None
    # Fault handling (docs/robustness.md). ``fault_injector``: a
    # repro.testing.faults.FaultInjector polled at this engine's named
    # sites, keyed by the dispatch ordinal (None in production).
    # Transient step failures are retried up to ``max_step_retries``
    # with exponential backoff (base ``retry_backoff_s``, 0 = no sleep);
    # an exhausted panel FAILS GRACEFULLY: step returns (None, stats)
    # naming the lost request ids instead of raising.
    fault_injector: Any = None
    max_step_retries: int = 2
    retry_backoff_s: float = 0.0
    # Per-request NaN quarantine: after each step, non-finite output
    # columns fail only their own request ids (stats carry them as
    # ``quarantined_request_ids``); the rest of the panel is served.
    quarantine_nonfinite: bool = True
    # Validate sparse layout invariants at construction (sorted
    # in-bounds indices, finite values — see BlockCSRMatrix.validate).
    # Trust boundary only; the per-step hot path never re-checks.
    validate: bool = True
    # Time source for retry backoff (repro.serve.clock): None = real
    # wall clock. Tests and the bench inject a VirtualClock so a
    # backoff-heavy faulted trace neither stalls CI nor depends on
    # runner load.
    clock: Any = None
    # Kernel autotuning (docs/tuning.md). ``tuning_table``: a
    # repro.tune.TuningTable consulted ONCE at construction by this
    # stack's topology fingerprint — a hit threads the tuned config
    # (block size, block_n, forced layout) through every plan this
    # engine builds; a miss serves defaults, silently. The sharded
    # level always serves untuned (the sharded builder takes no tuning
    # knobs).
    tuning_table: Any = None

    def __post_init__(self):
        self.n_layers = len(self.weights)
        if len(self.biases) != self.n_layers:
            raise ValueError("weights/biases length mismatch")
        # Fingerprint once — weights are immutable across requests; the
        # hot path must not re-hash the topology per step. The
        # tuning-table lookup is keyed by it too.
        self._fingerprint = topology_fingerprint(tuple(self.weights))
        self._tuned = None
        if self.tuning_table is not None:
            dtype = str(
                np.dtype(getattr(self.weights[0], "dtype", np.float32))
            )
            self._tuned = self.tuning_table.lookup(
                self._fingerprint, dtype=dtype
            )
        if self.validate:
            checked: set[int] = set()  # layers may repeat one object
            for i, w in enumerate(self.weights):
                if hasattr(w, "validate") and id(w) not in checked:
                    w.validate(name=f"SparseDNNEngine layer {i} weight")
                    checked.add(id(w))
        if self.plan_cache is None:
            self.plan_cache = PlanCache(max_size=16)
        # The degradation ladder owns execution-level health: sharded →
        # single-device layered fallback for the same fingerprint.
        self._ladder = DegradationLadder(
            self.plan_cache, mesh=self.mesh, tuned=self._tuned
        )
        self._served = 0
        self._steps = 0
        self._dispatches = 0  # fault sites key on this ordinal
        self._next_rid = 0
        # Staged work is kept as contiguous (request_ids, panel) chunks —
        # a chunk is only split when a step's limit lands inside it, so
        # the one-shot infer path stays a single pad on the caller's
        # array with no per-column slicing.
        self._staged: list[tuple[list, Array]] = []
        self._staged_count = 0

    @property
    def ladder(self) -> DegradationLadder:
        """The engine's degradation ladder (health marks, events)."""
        return self._ladder

    @property
    def tuned(self):
        """The resolved tuned config this engine serves with (None =
        defaults; see ``repro.tune``)."""
        return self._tuned

    def _plan_for_width(self, width: int, *, step: int = -1, compile_hook=None):
        """(plan, level, cache_hit) serving a ``width``-wide panel at
        the best healthy degradation level. Route rules are the plan
        layer's (layered per-layout kernels; dense layers keep jax.grad
        compatibility under ``differentiable=True`` via the XLA form);
        the ladder only decides WHICH level to serve at when the mesh is
        marked unhealthy."""
        with spans.span("engine.plan", width=width) as s:
            plan, level, hit = self._ladder.get_plan(
                tuple(self.weights),
                tuple(self.biases),
                width,
                differentiable=self.differentiable,
                fingerprint=self._fingerprint,
                step=step,
                compile_hook=compile_hook,
            )
            s.set(level=level, hit=hit)
        return plan, level, hit

    # ------------------------------------------------------------------
    # step-level API (driven by serve.scheduler.ContinuousBatcher)
    # ------------------------------------------------------------------

    @property
    def staged(self) -> int:
        """Feature columns submitted but not yet dispatched."""
        return self._staged_count

    @property
    def staged_request_ids(self) -> list:
        return [rid for rids, _ in self._staged for rid in rids]

    def submit(
        self, cols: Array, request_ids: Sequence[Any] | None = None
    ) -> list:
        """Stage (m, k) feature columns for the next ``step``.

        Returns the request ids assigned to the k columns (monotonic
        ints unless the caller names them). Staging is pure bookkeeping
        — no kernel work happens until ``step``.
        """
        m, k = cols.shape
        if request_ids is None:
            request_ids = list(range(self._next_rid, self._next_rid + k))
            self._next_rid += k
        elif len(request_ids) != k:
            raise ValueError(
                f"{len(request_ids)} request ids for {k} columns"
            )
        if k:
            self._staged.append((list(request_ids), cols))
            self._staged_count += k
        return list(request_ids)

    def _idle_stats(self) -> dict:
        return {
            "batch": 0,
            "padded_batch": 0,
            "pad_slots": 0,
            "grid_steps": 0,
            "request_ids": [],
            "differentiable": self.differentiable,
            "pallas_calls": 0,
            "served_total": self._served,
            "engine_steps": self._steps,
            "plan": None,
            "failed": False,
            "retries": 0,
            "quarantined_request_ids": [],
        }

    def step(
        self, limit: int | None = None, *, pad_to: int | None = None
    ) -> tuple[Array | None, dict]:
        """Dispatch ONE padded forward pass over up to ``limit`` staged
        columns (FIFO). Returns ``(Y[L] (m, batch), stats)``; stats carry
        the exact grid-step bill for the padded panel, so idle pad slots
        are visible as kernel steps. ``(None, stats)`` when nothing is
        staged.

        ``pad_to`` pads the panel further, up to that width (itself
        aligned to ``batch_align``) — the scheduler's width-class
        quantization hook: panels padded to a shared class width reuse
        one compiled plan instead of compiling per distinct width.
        """
        if limit is not None and limit < 1:
            raise ValueError(f"step limit must be >= 1, got {limit}")
        if pad_to is not None and pad_to < 1:
            raise ValueError(f"pad_to must be >= 1, got {pad_to}")
        batch = (
            self._staged_count
            if limit is None
            else min(limit, self._staged_count)
        )
        if batch == 0:
            return None, self._idle_stats()
        width = batch + (-batch) % self.batch_align
        if pad_to is not None:
            width = max(width, pad_to + (-pad_to) % self.batch_align)
        ordinal = self._dispatches
        self._dispatches += 1
        with spans.span(
            "engine.step", ordinal=ordinal, batch=batch, width=width
        ):
            return self._dispatch(batch, width, ordinal)

    def _pop_staged(self, batch: int) -> list[tuple[list, Array]]:
        """The chunks holding the first ``batch`` staged columns, FIFO."""
        need = batch
        take: list[tuple[list, Array]] = []
        while need:
            rids, arr = self._staged[0]
            k = arr.shape[1]
            if k <= need:
                take.append(self._staged.pop(0))
                need -= k
            else:  # split the chunk at the step boundary
                take.append((rids[:need], arr[:, :need]))
                self._staged[0] = (rids[need:], arr[:, need:])
                need = 0
        self._staged_count -= batch
        return take

    def _dispatch(
        self, batch: int, width: int, ordinal: int
    ) -> tuple[Array | None, dict]:
        """The body of ``step`` once the panel's width is known."""
        with spans.span("engine.stage") as s:
            take = self._pop_staged(batch)
            s.set(chunks=len(take))
            ids = [rid for rids, _ in take for rid in rids]
            yp = (
                take[0][1]
                if len(take) == 1
                else jnp.concatenate([arr for _, arr in take], axis=1)
            )
        # ---- fault sites (docs/robustness.md), keyed by dispatch ordinal
        inj = self.fault_injector
        compile_spec = transient_spec = None
        if inj is not None:
            if inj.fires(_faults.SITE_CACHE_EVICTION, ordinal) is not None:
                self.plan_cache.clear()  # eviction storm: every width recompiles
            spec = inj.fires(_faults.SITE_SHARD_FAILURE, ordinal)
            if spec is not None and self.mesh is not None:
                self._ladder.mark_unhealthy(
                    "sharded",
                    reason=spec.get("reason", "injected shard failure"),
                    step=ordinal,
                )
            spec = inj.fires(_faults.SITE_PANEL_NANS, ordinal)
            if spec is not None:
                # poison only real request columns — pad stays clean
                yp, _ = _faults.poison_panel(
                    yp, limit=batch, rng=inj.rng, **spec
                )
            compile_spec = inj.fires(_faults.SITE_PLAN_COMPILE, ordinal)
            transient_spec = inj.fires(_faults.SITE_STEP_TRANSIENT, ordinal)
        failures_left = (
            int(transient_spec.get("failures", 1)) if transient_spec else 0
        )

        def compile_hook(level: str) -> None:
            nonlocal compile_spec
            if compile_spec is not None:
                compile_spec = None  # fires once, at the preferred level
                raise _faults.InjectedFault(
                    f"injected plan-compile failure at level {level!r}"
                )

        out = None
        retries = 0
        last_err: Exception | None = None
        plan = level = cache_hit = None
        for attempt in range(self.max_step_retries + 1):
            try:
                plan, level, cache_hit = self._plan_for_width(
                    width, step=ordinal, compile_hook=compile_hook
                )
                if failures_left > 0:
                    failures_left -= 1
                    raise _faults.TransientFault(
                        "injected transient step failure"
                    )
                with spans.span("engine.dispatch") as s:
                    compiles = plan.compile_count
                    out = plan.forward(yp)
                    s.set(compiled=plan.compile_count > compiles)
                break
            except _faults.TransientFault as e:
                last_err = e
                if attempt >= self.max_step_retries:
                    break
                retries += 1
                if self.retry_backoff_s:
                    (self.clock or WALL_CLOCK).sleep(
                        self.retry_backoff_s * 2**attempt
                    )
            except Exception as e:  # noqa: BLE001 — not retryable
                last_err = e
                break
        if out is None:
            # Graceful panel failure: the batch's requests are lost, the
            # engine (and the requests behind it) live on.
            stats = self._idle_stats()
            stats.update(
                batch=batch,
                request_ids=ids,
                failed=True,
                retries=retries,
                error=f"{type(last_err).__name__}: {last_err}",
            )
            return None, stats
        self._served += batch
        self._steps += 1
        res = out[:, :batch]
        quarantined: list = []
        if self.quarantine_nonfinite:
            # The host waits here for the device to finish the panel.
            with spans.span("engine.finite_sync"):
                if not bool(jnp.isfinite(res).all()):
                    col_ok = np.asarray(jnp.isfinite(res).all(axis=0))
                    quarantined = [
                        ids[j] for j in range(batch) if not col_ok[j]
                    ]
        plan_stats = {
            "width_class": width,
            "cache_hit": cache_hit,
            "route": plan.route,
            "compiles": plan.compile_count,
            "level": level,
            "degraded": level != self._ladder.preferred_level,
            "tuned": plan.key.tuned,
        }
        if getattr(plan, "is_sharded", False):
            # Per-shard accounting: each shard's bill is its local
            # segment length × column tiles; they sum to plan.grid_steps
            # (= the unsharded occupancy-exact bill when shard counts
            # divide the stored blocks evenly).
            plan_stats["shards"] = plan.n_shards
            plan_stats["grid_steps_per_shard"] = list(
                plan.grid_steps_per_shard
            )
        stats = {
            "batch": batch,
            "padded_batch": width,
            "pad_slots": width - batch,
            "grid_steps": plan.grid_steps,
            "request_ids": ids,
            "differentiable": self.differentiable,
            "pallas_calls": plan.pallas_calls,
            "served_total": self._served,
            "engine_steps": self._steps,
            "plan": plan_stats,
            "failed": False,
            "retries": retries,
            "quarantined_request_ids": quarantined,
        }
        return res, stats

    def drain(self, limit: int | None = None) -> list[tuple[Array, dict]]:
        """Step until the stage is empty (≤ ``limit`` columns per step)."""
        results = []
        while self._staged:
            results.append(self.step(limit))
        return results

    def infer(self, y0: Array) -> tuple[Array, dict]:
        """One-shot API: y0 (m, batch) feature columns → (Y[L], stats).

        A thin wrapper over ``submit`` + ``step`` — one aligned,
        right-padded batch per call, exactly the pre-scheduler contract.
        """
        m, batch = y0.shape
        if batch == 0:
            return y0, self._idle_stats()
        if self._staged:
            raise RuntimeError(
                "infer() on an engine with staged columns would reorder "
                "them past the step API's FIFO; call drain() first"
            )
        self.submit(y0)
        out, stats = self.step()
        return out, stats


def make_serve_fns(model: Model):
    """(prefill_fn, decode_fn) suitable for jit/lower — the functions the
    dry-run compiles for the decode-shape cells."""

    def prefill_fn(params, tokens, cache):
        return model.prefill(params, tokens, cache)

    def decode_fn(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)

    return prefill_fn, decode_fn
