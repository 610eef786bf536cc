"""Mesh-sharded sparse stacks: the balanced block-CSR partitioner,
PartitionSpec resolution through the sharding rule table, plan-cache
keying on the mesh fingerprint, and the shard_map execution path.

Partitioner and cache-keying tests are device-free / single-device.
Multi-device numerics (the acceptance bar: sharded forward/backward ==
single-device plan path, serve parity, per-shard bills summing to the
unsharded bill) run twice: in a SUBPROCESS with 8 fake host devices so
the tier-1 suite covers them on any machine (dry-run contract — the
main process keeps its single-device view), and in-process when the
interpreter already has ≥ 8 devices (the CI multi-device job sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import plan as PL
from repro.core import dnn
from repro.distribution.sharding import (
    ShardingRules,
    mesh_shard_count,
    row_block_axes,
    sharded_csr_pspecs,
)
from repro.launch.mesh import make_row_blocks_mesh
from repro.serve import SparseDNNEngine
from repro.sparse import (
    BlockCSRMatrix,
    BlockSparseMatrix,
    partition_block_csr,
    stack_transpose_plans,
)


def _csr_stack(seed, L, m, bpr=4, block=16, scale=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), L)
    ws = []
    for k in ks:
        w = BlockSparseMatrix.random(
            k, (m, m), (block, block), blocks_per_row=bpr,
            minval=-0.5, maxval=0.5,
        )
        if scale:
            w = w.map_blocks(lambda x: x / (bpr * block) ** 0.5)
        ws.append(BlockCSRMatrix.from_bsr(w))
    bs = [jnp.full((m,), 0.01 * i, jnp.float32) for i in range(L)]
    return ws, bs


# ---------------------------------------------------------------------
# partitioner (host-side — no devices involved)
# ---------------------------------------------------------------------


def test_partition_balances_and_reassembles():
    a = BlockCSRMatrix.random_skewed(
        seed=3, shape=(128, 128), block_shape=(16, 16),
        total_blocks=40, skew=0.9,
    )
    sh = partition_block_csr(a, 8)
    assert sh.n_shards == 8
    assert sh.imbalance() <= 1.10  # the acceptance bar
    assert int(sh.nnz_per_shard().sum()) == int(a.nnz_blocks)
    # every stored block lands in exactly one shard → the sum of the
    # per-shard densifications reassembles the original matrix
    np.testing.assert_allclose(
        np.asarray(sh.to_dense()), np.asarray(a.to_dense())
    )


def test_partition_degenerate_zero_nnz_shards():
    """Regression (satellite): a shard receiving zero nnz blocks for a
    very sparse topology must become an empty sub-layout, not a crash."""
    m, block = 64, 16
    dense = jnp.zeros((m, m)).at[:block, :block].set(1.0)
    a = BlockCSRMatrix.from_dense(dense, (block, block))  # 1 stored block
    sh = partition_block_csr(a, 8)
    nnz = sh.nnz_per_shard()
    assert int(nnz.sum()) == 1 and (nnz == 0).sum() == 7
    # empty shards: all-invalid slots, all-zero row_ptr (every row reads
    # empty → the kernel wrapper fills the semiring zero, psum-neutral)
    for s in range(1, 8):
        local = sh.shard(s)
        assert not bool(np.asarray(local.valid).any())
        assert np.asarray(local.row_ptr).max() == 0
    np.testing.assert_allclose(
        np.asarray(sh.to_dense()), np.asarray(dense)
    )
    with pytest.raises(ValueError, match="n_shards"):
        partition_block_csr(a, 0)


def test_partition_rescatter_roundtrip_and_grad():
    ws, _ = _csr_stack(1, 1, 64)
    a = ws[0]
    sh = partition_block_csr(a, 4)
    # frozen-partition gather reproduces the partitioned values
    np.testing.assert_allclose(
        np.asarray(sh.rescatter_values(a.values)), np.asarray(sh.values)
    )
    # its VJP scatters back onto the unsharded layout (training route)
    g = jax.grad(lambda v: jnp.sum(sh.rescatter_values(v) ** 2))(a.values)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(2.0 * a.values), rtol=1e-6
    )


def test_stacked_transpose_plans_match_per_shard():
    ws, _ = _csr_stack(2, 1, 64)
    sh = partition_block_csr(ws[0], 4)
    stacked = stack_transpose_plans(sh)
    assert stacked.order.shape[0] == 4
    for s in range(4):
        ref = sh.shard(s).transpose()
        from repro.sparse import BcsrTransposePlan

        local = BcsrTransposePlan(
            stacked.order[s], stacked.row_ptr[s], stacked.row_id[s],
            stacked.col_idx[s], stacked.valid[s],
            stacked.shape, stacked.block_shape,
        )
        got = local.apply(sh.shard(s))
        np.testing.assert_allclose(
            np.asarray(got.to_dense()), np.asarray(ref.to_dense())
        )


# ---------------------------------------------------------------------
# rule-table resolution of the row_blocks axis
# ---------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape: dict):
        self.shape = shape


def test_row_block_axes_prefers_dedicated_axis():
    assert row_block_axes(_FakeMesh({"row_blocks": 8})) == ("row_blocks",)
    assert mesh_shard_count(_FakeMesh({"row_blocks": 8})) == 8
    # compute meshes without the dedicated axis: every compute axis
    assert row_block_axes(_FakeMesh({"data": 4, "model": 2})) == (
        "data", "model",
    )
    assert mesh_shard_count(_FakeMesh({"data": 4, "model": 2})) == 8
    # nothing matches → unsharded (1 shard)
    assert row_block_axes(_FakeMesh({"pod": 2})) == ()
    assert mesh_shard_count(_FakeMesh({"pod": 2})) == 1
    # rules are honored: dropping the tp axis halves the shard count
    rules = ShardingRules(tp_axis=None)
    assert row_block_axes(_FakeMesh({"data": 4, "model": 2}), rules) == (
        "data",
    )


def test_sharded_csr_pspecs_resolve_leading_shard_dim():
    ws, _ = _csr_stack(4, 1, 64)
    sh = partition_block_csr(ws[0], 8)
    specs = sharded_csr_pspecs(sh, _FakeMesh({"row_blocks": 8}))
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    assert len(leaves) == 6  # one per ShardedBlockCSR leaf
    for spec in leaves:
        assert tuple(spec) == ("row_blocks",)  # dim0 sharded, rest local
    # divisibility fallback: a mesh whose axes cannot divide the shard
    # count replicates instead of mis-sharding
    specs = sharded_csr_pspecs(sh, _FakeMesh({"data": 3, "model": 1}))
    for spec in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    ):
        assert tuple(spec) in ((), (None,))


# ---------------------------------------------------------------------
# plan-cache keying (satellite): mesh fingerprint in PlanKey
# ---------------------------------------------------------------------


def test_plan_key_carries_mesh_fingerprint():
    mesh = make_row_blocks_mesh(1)  # 1 device is enough for keying
    fp = PL.mesh_fingerprint(mesh)
    assert fp.startswith("row_blocks[")
    ws, bs = _csr_stack(5, 2, 64)
    cache = PL.PlanCache(max_size=8)
    unsharded = cache.get(ws, bs, 8)
    sharded = cache.get(ws, bs, 8, mesh=mesh)
    # same topology, same width — the mesh fingerprint keeps the keys
    # (and hence the compiled executables) apart
    assert unsharded is not sharded
    assert unsharded.key.mesh is None and sharded.key.mesh == fp
    assert cache.stats()["builds"] == 2
    # and each key still hits on repeat
    assert cache.get(ws, bs, 8, mesh=mesh) is sharded
    assert cache.get(ws, bs, 8) is unsharded
    assert cache.stats()["hits"] == 2


def test_default_cache_reset_helper():
    PL.reset_default_cache()
    cache = PL.default_cache()
    ws, bs = _csr_stack(6, 1, 64)
    cache.get(ws, bs, 8)
    assert cache.stats()["builds"] == 1
    PL.reset_default_cache()
    fresh = PL.default_cache()
    assert fresh is not cache
    assert fresh.stats() == {
        "size": 0, "max_size": 4, "lookups": 0, "hits": 0, "misses": 0,
        "builds": 0, "evictions": 0, "hit_rate": 0.0,
    }


def test_sharded_plan_donor_shares_partition_across_widths():
    mesh = make_row_blocks_mesh(1)
    ws, bs = _csr_stack(7, 2, 64)
    cache = PL.PlanCache(max_size=8)
    p8 = cache.get(ws, bs, 8, mesh=mesh, differentiable=True)
    p16 = cache.get(ws, bs, 16, mesh=mesh, differentiable=True)
    assert p16.layers[0].sharded is p8.layers[0].sharded
    assert p16.layers[0].transpose is p8.layers[0].transpose
    assert p16.grid_steps == dnn.dnn_grid_steps(ws, 16)  # width-local


# ---------------------------------------------------------------------
# execution on whatever mesh this process can build (1 shard here;
# the 8-shard run happens in the subprocess / CI multi-device job)
# ---------------------------------------------------------------------


def test_sharded_plan_forward_matches_reference_one_shard():
    mesh = make_row_blocks_mesh(1)
    ws, bs = _csr_stack(8, 3, 64)
    plan = PL.build_sharded_plan(ws, bs, 8, mesh)
    assert plan.route == PL.ROUTE_SHARDED
    assert plan.grid_steps == dnn.dnn_grid_steps(ws, 8)
    assert sum(plan.grid_steps_per_shard) == plan.grid_steps
    y0 = jax.random.uniform(jax.random.PRNGKey(9), (64, 5))
    np.testing.assert_allclose(
        np.asarray(plan.forward(y0)),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5, atol=1e-5,
    )
    assert plan.compile_count == 1
    plan.forward(y0)
    assert plan.compile_count == 1  # same width class → same executable
    with pytest.raises(ValueError, match="width"):
        plan.forward(jnp.zeros((64, 9)))


def test_sharded_plan_rejects_resident_and_ell_differentiable():
    mesh = make_row_blocks_mesh(1)
    _, bs = _csr_stack(10, 1, 64)
    ell = [BlockSparseMatrix.random(
        jax.random.PRNGKey(0), (64, 64), (16, 16), blocks_per_row=2
    )]
    with pytest.raises(ValueError, match="block-CSR"):
        PL.build_sharded_plan(ell, bs, 8, mesh, differentiable=True)
    # inference plans re-lay ELL to CSR instead
    plan = PL.build_sharded_plan(ell, bs, 8, mesh)
    assert plan.layers[0].source_layout == "ell"
    assert plan.layers[0].kind == "bcsr"


def test_engine_mesh_rejects_resident_and_reports_shards():
    mesh = make_row_blocks_mesh(1)
    ws, bs = _csr_stack(11, 2, 64)
    eng = SparseDNNEngine(ws, bs, batch_align=8, mesh=mesh)
    y0 = jax.random.uniform(jax.random.PRNGKey(12), (64, 5))
    out, stats = eng.infer(y0)
    assert stats["plan"]["route"] == PL.ROUTE_SHARDED
    assert stats["plan"]["shards"] == 1
    assert sum(stats["plan"]["grid_steps_per_shard"]) == stats["grid_steps"]
    ref = SparseDNNEngine(ws, bs, batch_align=8).infer(y0)[0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------
# 8-shard numerics — the acceptance bar
# ---------------------------------------------------------------------

# Runs on an 8-host-device mesh: choose nnz_blocks divisible by 8 so
# the per-shard bills sum EXACTLY to the unsharded occupancy-exact bill
# (no Tp-padding remainder) — the accounting the serve stats expose.
_MULTIDEVICE_BODY = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.core import dnn
    from repro.launch.mesh import make_row_blocks_mesh
    from repro.plan import build_sharded_plan
    from repro.serve import ContinuousBatcher, SparseDNNEngine
    from repro.sparse import BlockCSRMatrix, BlockSparseMatrix
    from repro.train.optimizer import sgd
    from repro.train.sparse import init_sparse_mlp_state, make_sparse_train_step

    assert len(jax.devices()) >= 8, jax.devices()
    mesh = make_row_blocks_mesh(8)
    m, L, block, bpr = 64, 3, 16, 4  # nnz = 16 blocks/layer → 8 | 16
    ws = []
    for i in range(L):
        w = BlockSparseMatrix.random(
            jax.random.PRNGKey(i), (m, m), (block, block), blocks_per_row=bpr,
            minval=-0.5, maxval=0.5,
        ).map_blocks(lambda x: x / (bpr * block) ** 0.5)
        ws.append(BlockCSRMatrix.from_bsr(w))
    bs = [jnp.full((m,), 0.01 * i, jnp.float32) for i in range(L)]
    y0 = jax.random.uniform(jax.random.PRNGKey(99), (m, 8), jnp.float32)

    # forward: sharded == single-device plan path == dense reference
    plan = build_sharded_plan(ws, bs, 8, mesh)
    assert plan.n_shards == 8
    assert plan.imbalance() <= 1.10, plan.imbalance()
    out = np.asarray(plan.forward(y0))
    np.testing.assert_allclose(
        out, np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5, atol=1e-5,
    )
    dense_ref = y0
    for w, b in zip(ws, bs):
        dense_ref = jnp.maximum(w.to_dense() @ dense_ref + b[:, None], 0)
    np.testing.assert_allclose(out, np.asarray(dense_ref), rtol=1e-4, atol=1e-5)
    # per-shard bills sum to the unsharded occupancy-exact bill
    assert sum(plan.grid_steps_per_shard) == dnn.dnn_grid_steps(ws, 8), (
        plan.grid_steps_per_shard, dnn.dnn_grid_steps(ws, 8))
    assert plan.shard_pad_blocks() == 0
    print("forward8 OK")

    # backward: grads through the sharded plan match the legacy path
    targets = jnp.asarray(dense_ref) * 0.5
    dplan = build_sharded_plan(ws, bs, 8, mesh, differentiable=True)
    l1, (dw1, db1) = dnn.dnn_value_and_grad(ws, bs, y0, targets, plan=dplan)
    l2, (dw2, db2) = dnn.dnn_value_and_grad(ws, bs, y0, targets)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(dw1, dw2):
        np.testing.assert_allclose(
            np.asarray(a.values), np.asarray(b.values), rtol=1e-4, atol=1e-7)
    for a, b in zip(db1, db2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-7)
    print("grads8 OK")

    # a degenerate-shard topology must execute, not just partition
    tiny = [BlockCSRMatrix.from_dense(
        jnp.zeros((m, m)).at[:16, :32].set(0.25), (16, 16))]
    tb = [jnp.zeros((m,), jnp.float32)]
    tp = build_sharded_plan(tiny, tb, 8, mesh)
    assert (tp.layers[0].sharded.nnz_per_shard() == 0).sum() == 6
    np.testing.assert_allclose(
        np.asarray(tp.forward(y0)),
        np.asarray(dnn.dnn_forward(tiny, tb, y0, fused=True)),
        rtol=1e-5, atol=1e-5,
    )
    print("degenerate8 OK")

    # serve: the sharded engine reproduces single-device outputs with
    # per-shard accounting summing to the unsharded bill
    e0 = SparseDNNEngine(ws, bs, batch_align=8)
    e1 = SparseDNNEngine(ws, bs, batch_align=8, mesh=mesh)
    for k in (3, 8, 5):
        y = jax.random.uniform(jax.random.PRNGKey(100 + k), (m, k))
        o0, s0 = e0.infer(y)
        o1, s1 = e1.infer(y)
        np.testing.assert_allclose(
            np.asarray(o0), np.asarray(o1), rtol=1e-5, atol=1e-5)
        assert s1["plan"]["shards"] == 8
        assert sum(s1["plan"]["grid_steps_per_shard"]) == s0["grid_steps"], (
            s1["plan"], s0["grid_steps"])
    b = ContinuousBatcher(e1, batch_size=16, min_fill=0.0, width_classes=(8, 16))
    cols = {}
    for i in range(5):
        for j in range(1 + i % 3):
            col = jax.random.uniform(jax.random.PRNGKey(200 + 10 * i + j), (m,))
            cols[b.submit(col)] = col
        b.step(force=True)
    b.drain()
    for rid, col in cols.items():
        np.testing.assert_allclose(
            np.asarray(b.result(rid)),
            np.asarray(dnn.dnn_forward(ws, bs, col[:, None], fused=True)[:, 0]),
            rtol=1e-5, atol=1e-5)
    print("serve8 OK")

    # train: the sharded step's losses track the legacy step exactly
    batch = {"y0": y0, "targets": targets}
    opt = sgd(0.5, momentum=0.0)
    step_s = jax.jit(make_sparse_train_step(opt, use_kernel=True, plan=dplan))
    step_l = jax.jit(make_sparse_train_step(opt, use_kernel=True))
    st_s = init_sparse_mlp_state(ws, bs, opt)
    st_l = init_sparse_mlp_state(ws, bs, opt)
    losses_s, losses_l = [], []
    for _ in range(4):
        st_s, ms = step_s(st_s, batch)
        st_l, ml = step_l(st_l, batch)
        losses_s.append(float(ms["loss"]))
        losses_l.append(float(ml["loss"]))
    assert np.allclose(losses_s, losses_l, rtol=1e-5), (losses_s, losses_l)
    assert losses_s[-1] < losses_s[0]
    print("train8 OK")
    """
)

_SUBPROC = (
    "import os\n"
    'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
    + _MULTIDEVICE_BODY
)

_MARKS = ("forward8", "grads8", "degenerate8", "serve8", "train8")


@pytest.mark.slow
def test_multidevice_sharding_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    for mark in _MARKS:
        assert f"{mark} OK" in r.stdout, r.stdout


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(the CI multi-device job sets it)",
)
def test_multidevice_sharding_inprocess(capsys):
    exec(compile(_MULTIDEVICE_BODY, "<multidevice-sharding>", "exec"), {})
    out = capsys.readouterr().out
    for mark in _MARKS:
        assert f"{mark} OK" in out
