"""Kernel autotuner (``repro.tune``): tuning-table round-trip and schema
gating (a table naming a removed knob is refused), fingerprint
isolation, deterministic sweep selection (block-CSR forcing strictly
beats ELL on a skewed stack; the accuracy gate rejects re-blocked
candidates at zero tolerance), PlanCache tuned/untuned non-collision,
and the engine-side table lookup."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as P
from repro.serve import SparseDNNEngine
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix
from repro.tune import (
    SCHEMA_VERSION,
    TunedConfig,
    TuningTable,
    TuningTableError,
    default_candidates,
    sweep_stack,
    tune_stack,
)


def _square_stack(key, L=3, m=64, bpr=2, block=16):
    ks = jax.random.split(key, L)
    ws = [
        BlockSparseMatrix.random(k, (m, m), (block, block), blocks_per_row=bpr)
        for k in ks
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in range(L)]
    return ws, bs


def _skewed_stack():
    """Rectangular (→ layered route) skewed stack whose ELL waste stays
    UNDER the 0.25 relayout threshold — the default plan keeps ELL, yet
    forcing block-CSR strictly drops the grid-step bill."""
    specs = [((128, 256), 100), ((128, 128), 55), ((64, 128), 28)]
    ws = []
    for i, (shape, tb) in enumerate(specs):
        w = BlockCSRMatrix.random_skewed(
            i, shape, (16, 16), tb, skew=0.3
        ).to_bsr()
        nrb, mbpr = w.col_idx.shape
        assert 1 - tb / (nrb * mbpr) < P.ELL_WASTE_THRESHOLD
        assert nrb * mbpr > tb  # ELL pays pad the CSR grid skips
        ws.append(w)
    bs = [jnp.zeros((s[0],), jnp.float32) for s, _ in specs]
    return ws, bs


# ---------------------------------------------------------------- table


class TestTunedConfig:
    def test_default_token(self):
        assert TunedConfig().token() == "default"
        assert TunedConfig().is_default

    def test_token_deterministic_and_distinct(self):
        a = TunedConfig(block_n=64, layout="bcsr")
        b = TunedConfig(block_n=64, layout="bcsr")
        c = TunedConfig(block_n=64)
        assert a.token() == b.token()
        assert a.token() != c.token()

    def test_bad_layout_rejected(self):
        with pytest.raises(ValueError, match="layout"):
            TunedConfig(layout="csc")

    def test_dict_round_trip(self):
        cfg = TunedConfig(block_size=32, layout="bcsr")
        assert TunedConfig.from_dict(cfg.to_dict()) == cfg

    def test_unknown_knob_rejected(self):
        with pytest.raises(TuningTableError, match="unknown"):
            TunedConfig.from_dict({"warp_size": 32})


class TestTuningTable:
    def test_round_trip(self, tmp_path):
        table = TuningTable()
        cfg = TunedConfig(layout="bcsr", block_n=64)
        table.put("fp1", "cpu", "float32", cfg, {"grid_steps": 7})
        path = tmp_path / "table.json"
        table.save(path)
        loaded = TuningTable.load(path)
        assert loaded.lookup("fp1", backend="cpu") == cfg
        assert loaded.record("fp1", backend="cpu")["grid_steps"] == 7

    def test_fingerprint_isolation(self):
        table = TuningTable()
        table.put("fpA", "cpu", "float32", TunedConfig(block_n=64))
        assert table.lookup("fpB", backend="cpu") is None
        assert table.lookup("fpA", backend="tpu") is None
        assert table.lookup("fpA", backend="cpu", dtype="bfloat16") is None
        assert table.lookup("fpA", backend="cpu") == TunedConfig(block_n=64)

    def test_schema_version_rejected(self, tmp_path):
        path = tmp_path / "stale.json"
        path.write_text(
            json.dumps({"schema_version": SCHEMA_VERSION + 1, "entries": {}})
        )
        with pytest.raises(TuningTableError, match="schema_version"):
            TuningTable.load(path)

    def test_corrupt_rejected(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text("{not json")
        with pytest.raises(TuningTableError):
            TuningTable.load(path)
        path.write_text(json.dumps({"schema_version": SCHEMA_VERSION}))
        with pytest.raises(TuningTableError, match="entries"):
            TuningTable.load(path)
        path.write_text(
            json.dumps(
                {
                    "schema_version": SCHEMA_VERSION,
                    "entries": {"k": {"config": {"warp_size": 4}}},
                }
            )
        )
        with pytest.raises(TuningTableError, match="unknown"):
            TuningTable.load(path)

    @pytest.mark.parametrize("knob", ["panel_dtype", "vmem_limit_bytes"])
    def test_a_tuning_table_naming_a_removed_knob_is_refused(
        self, tmp_path, knob
    ):
        """Knobs of the retired whole-stack kernel name nothing the plan
        reads: a table carrying one is refused on load, naming it."""
        value = {"panel_dtype": "bfloat16", "vmem_limit_bytes": 1 << 23}[knob]
        path = tmp_path / "old.json"
        path.write_text(
            json.dumps(
                {
                    "schema_version": SCHEMA_VERSION,
                    "entries": {
                        "fp:cpu:float32": {
                            "config": {"block_n": 64, knob: value}
                        }
                    },
                }
            )
        )
        with pytest.raises(TuningTableError, match=knob):
            TuningTable.load(path)
        with pytest.raises(TuningTableError, match=knob):
            TunedConfig.from_dict({knob: value})


# ---------------------------------------------------------------- sweep


class TestSweep:
    def test_default_candidate_enumerated_first(self):
        cands = default_candidates()
        assert cands[0].is_default
        tokens = [c.token() for c in cands]
        assert len(tokens) == len(set(tokens))

    def test_bcsr_forcing_wins_on_skewed_stack(self):
        ws, bs = _skewed_stack()
        winner, records = sweep_stack(ws, bs, 64, time_forwards=False)
        assert winner.layout == "bcsr"
        by_token = {r["token"]: r for r in records}
        assert (
            by_token["layout=bcsr"]["grid_steps"]
            < by_token["default"]["grid_steps"]
        )
        # Selection is recorded on exactly one candidate.
        assert sum(r.get("selected", False) for r in records) == 1

    def test_sweep_is_deterministic(self):
        ws, bs = _skewed_stack()
        w1, r1 = sweep_stack(ws, bs, 64, time_forwards=False)
        w2, r2 = sweep_stack(ws, bs, 64, time_forwards=False)
        assert w1 == w2
        assert [r["token"] for r in r1] == [r["token"] for r in r2]

    def test_accuracy_gate_rejects(self):
        ws, bs = _square_stack(jax.random.PRNGKey(0))
        # A zero tolerance still passes the default config (err == 0)
        # but rejects every candidate re-blocked to 8x8, whose kernel
        # adds each row's terms in another order.
        winner, records = sweep_stack(
            ws,
            bs,
            32,
            candidates=default_candidates(block_sizes=(None, 8)),
            time_forwards=False,
            accuracy_rtol=0.0,
        )
        assert winner.block_size is None
        reblocked = [r for r in records if "block_size=8" in r["token"]]
        assert reblocked and all(not r["ok"] for r in reblocked)
        assert all(r["max_abs_err"] > 0 for r in reblocked)

    def test_tune_stack_evidence(self):
        ws, bs = _square_stack(jax.random.PRNGKey(1))
        winner, table = tune_stack(ws, bs, 32, time_forwards=False)
        fp = P.topology_fingerprint(ws)
        rec = table.record(fp)
        assert rec is not None
        assert rec["grid_steps"] <= rec["default_grid_steps"]
        assert rec["config"] == winner.to_dict()
        assert table.lookup(fp) == winner


# ----------------------------------------------------- plan integration


class TestPlanIntegration:
    def test_plan_cache_tuned_untuned_non_collision(self):
        ws, bs = _square_stack(jax.random.PRNGKey(2))
        cache = P.PlanCache()
        tuned = TunedConfig(layout="bcsr")
        p_default = cache.get(ws, bs, 32)
        p_tuned = cache.get(ws, bs, 32, tuned=tuned)
        assert p_default is not p_tuned
        assert p_default.key != p_tuned.key
        assert p_default.key.tuned is None
        assert p_tuned.key.tuned == tuned.token()
        # Each keeps its own slot: re-lookups hit, no rebuild.
        builds = cache.stats()["builds"]
        assert cache.get(ws, bs, 32) is p_default
        assert cache.get(ws, bs, 32, tuned=tuned) is p_tuned
        assert cache.stats()["builds"] == builds

    def test_mesh_plus_tuned_rejected(self):
        ws, bs = _square_stack(jax.random.PRNGKey(3))
        cache = P.PlanCache()
        with pytest.raises(ValueError, match="single-device"):
            cache.get(
                ws, bs, 32, mesh=object(), tuned=TunedConfig(block_n=64)
            )

    def test_tuned_plan_outputs_match(self):
        ws, bs = _skewed_stack()
        x = jax.random.normal(jax.random.PRNGKey(4), (256, 32))
        p0 = P.build_plan(ws, bs, 32)
        p1 = P.build_plan(ws, bs, 32, tuned=TunedConfig(layout="bcsr"))
        assert p1.layouts == ("bcsr", "bcsr", "bcsr")
        np.testing.assert_allclose(
            np.asarray(p1.forward(x)), np.asarray(p0.forward(x)), rtol=1e-6
        )

    def test_reblocked_plan_outputs_match(self):
        ws, bs = _skewed_stack()
        x = jax.random.normal(jax.random.PRNGKey(5), (256, 32))
        p0 = P.build_plan(ws, bs, 32)
        p1 = P.build_plan(ws, bs, 32, tuned=TunedConfig(block_size=32))
        assert all(w.block_shape == (32, 32) for w in p1.weights)
        np.testing.assert_allclose(
            np.asarray(p1.forward(x)),
            np.asarray(p0.forward(x)),
            rtol=1e-4,
            atol=1e-4,
        )


# --------------------------------------------------- engine integration


class TestEngineIntegration:
    def test_engine_consults_table(self):
        ws, bs = _square_stack(jax.random.PRNGKey(7))
        _, table = tune_stack(ws, bs, 64, time_forwards=False)
        eng = SparseDNNEngine(ws, bs, batch_align=32, tuning_table=table)
        assert eng.tuned == table.lookup(P.topology_fingerprint(ws))
        x = jax.random.normal(jax.random.PRNGKey(8), (64, 20))
        out, stats = eng.infer(x)
        assert stats["plan"]["tuned"] == eng.tuned.token()
        ref_eng = SparseDNNEngine(ws, bs, batch_align=32)
        ref, ref_stats = ref_eng.infer(x)
        assert ref_stats["plan"]["tuned"] is None
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_engine_table_miss_serves_defaults(self):
        ws, bs = _square_stack(jax.random.PRNGKey(9))
        eng = SparseDNNEngine(
            ws, bs, batch_align=32, tuning_table=TuningTable()
        )
        assert eng.tuned is None
        _, stats = eng.infer(jnp.ones((64, 4), jnp.float32))
        assert stats["plan"]["tuned"] is None
