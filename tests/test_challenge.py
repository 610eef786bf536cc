"""GraphChallenge conformance suite (`docs/benchmarks.md`).

Ground truth is the pure-numpy gather reference in
``repro.data.radixnet``; every engine execution path — the layered
Pallas plan in the component layout and in the caller's 16×16 blocks,
the XLA forward, the streaming challenge driver, and the 8-device
sharded engine — must produce the SAME challenge answer set (bit-level
category agreement) on fixed-seed inputs. Small configs run in tier-1;
the official challenge shapes (1024×120, 4096×120) and the 16384-neuron
config are ``slow``-marked.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.plan as P
from repro.core import dnn
from repro.data import radixnet as rx
from repro.serve import run_challenge


# ---------------------------------------------------------------------
# Generator invariants
# ---------------------------------------------------------------------


@pytest.mark.parametrize("neurons", [32, 64, 256, 1024, 2048])
def test_connectivity_invariants(neurons):
    for layer in range(rx.num_phases(neurons) + 1):
        conn = rx.radixnet_connectivity(neurons, layer)
        assert conn.shape == (neurons, rx.FAN_IN)
        assert conn.dtype == np.int32
        assert conn.min() >= 0 and conn.max() < neurons
        # exact fan-in 32: no duplicate edges on any row
        sorted_cols = np.sort(conn, axis=1)
        assert (np.diff(sorted_cols, axis=1) > 0).all(), (neurons, layer)
        # regularity: fan-out is exactly 32 everywhere too
        counts = np.bincount(conn.reshape(-1), minlength=neurons)
        assert (counts == rx.FAN_IN).all(), (neurons, layer)
        # a phase cycle repeats exactly
        again = rx.radixnet_connectivity(
            neurons, layer + rx.num_phases(neurons)
        )
        np.testing.assert_array_equal(conn, again)


def test_full_mixing_across_one_phase_cycle():
    # composing num_phases consecutive layers connects neuron 0 to all
    n = 1024
    reach = np.zeros(n, bool)
    reach[0] = True
    for layer in range(rx.num_phases(n)):
        conn = rx.radixnet_connectivity(n, layer)
        reach = reach[conn].any(axis=1)
    assert reach.all()


def test_spec_constants():
    spec = rx.RadixNetSpec(1024, 120)
    assert spec.bias == rx.CHALLENGE_BIAS[1024] == -0.3
    assert spec.edges == 120 * 1024 * 32
    assert rx.RadixNetSpec(4096, 120).bias == -0.35
    assert rx.challenge_bias(2048) == -0.3  # nearest smaller size
    with pytest.raises(ValueError):
        rx.RadixNetSpec(1000, 10)  # not a power of two
    with pytest.raises(ValueError):
        rx.RadixNetSpec(16, 10)  # below fan-in


def test_conn_to_bsr_is_exact():
    for n in (64, 256):
        for layer in range(rx.num_phases(n)):
            conn = rx.radixnet_connectivity(n, layer)
            mat = rx.conn_to_bsr(conn)
            dense = np.zeros((n, n), np.float32)
            dense[
                np.repeat(np.arange(n), rx.FAN_IN), conn.reshape(-1)
            ] = rx.WEIGHT_VALUE
            np.testing.assert_array_equal(
                np.asarray(mat.to_dense()), dense
            )


def test_weights_stack_is_homogeneous_and_fused_eligible():
    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(256, 5))
    assert len({w.max_blocks_per_row for w in ws}) == 1
    assert len({(w.shape, w.block_shape) for w in ws}) == 1
    assert len(bs) == 5 and bs[0].shape == (256,)


def test_reference_matches_the_gather_definition():
    """The reference's sparse product, run in column chunks on a thread
    pool, is bit-identical to the plain per-layer gather definition
    (same terms, added in ``conn`` order, in float32)."""
    spec = rx.RadixNetSpec(256, 7)
    y0 = rx.radixnet_input_panel(256, 300, density=0.3, seed=4)  # 3 chunks
    conns = [rx.radixnet_connectivity(256, l) for l in range(spec.layers)]
    biases = [spec.bias] * spec.layers
    whole = rx.reference_forward(conns, biases, y0)
    y = y0
    for conn, b in zip(conns, biases):
        z = (y[conn] * np.float32(rx.WEIGHT_VALUE)).sum(axis=1, dtype=np.float32)
        y = np.maximum(z + np.float32(b), np.float32(0.0))
    np.testing.assert_array_equal(whole, y)


def test_input_panel_is_seeded_and_sparse():
    a = rx.radixnet_input_panel(256, 40, density=0.3, seed=7)
    b = rx.radixnet_input_panel(256, 40, density=0.3, seed=7)
    c = rx.radixnet_input_panel(256, 40, density=0.3, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert set(np.unique(a)) <= {0.0, 1.0}
    assert 0.2 < a.mean() < 0.4


# ---------------------------------------------------------------------
# Conformance: every execution path reproduces the numpy ground truth
# ---------------------------------------------------------------------


def _legs_small(spec, y0):
    """(name, final activations) for every single-device execution path."""
    ws, bs = rx.radixnet_weights(spec)
    yj = jnp.asarray(y0)
    layered = P.build_plan(ws, bs, y0.shape[1])
    blocks = P.build_plan(ws, bs, y0.shape[1], relayout=False)
    assert layered.route == blocks.route == P.ROUTE_LAYERED
    assert blocks.component_layers == 0
    return [
        ("layered-plan", layered.forward(yj)),
        ("layered-16x16", blocks.forward(yj)),
        ("xla", dnn.dnn_forward(ws, bs, yj, fused=True)),
    ]


@pytest.mark.parametrize(
    "neurons,layers", [(64, 4), (256, 7)], ids=["64x4", "256x7"]
)
def test_conformance_small(neurons, layers):
    spec = rx.RadixNetSpec(neurons, layers)
    y0 = rx.radixnet_input_panel(neurons, 24, density=0.3, seed=11)
    ref_y, ref_cats = rx.radixnet_reference(spec, y0)
    for name, out in _legs_small(spec, y0):
        out = np.asarray(out)
        np.testing.assert_allclose(
            out, ref_y, rtol=1e-4, atol=1e-6, err_msg=name
        )
        got = rx.reference_categories(out)
        assert np.array_equal(got, ref_cats), (name, got, ref_cats)


@pytest.mark.parametrize(
    "route,level", [("layered", "layered")], ids=["auto-layered"]
)
def test_challenge_driver_small(route, level):
    """The driver serves the RadiX-net stack on the layered route in the
    component layout."""
    spec = rx.RadixNetSpec(256, 6)
    _, ref_cats = rx.radixnet_reference(
        spec, rx.radixnet_input_panel(256, 50, density=0.3, seed=5)
    )
    res = run_challenge(
        spec, n_inputs=50, panel_width=24, batch_align=8, seed=5
    )
    assert np.array_equal(res.categories, ref_cats)
    assert res.served == 50
    assert res.steps == 3  # ceil(50 / 24) width-classed panels
    assert res.width_classes == (24,)  # one compiled class, incl. tail
    assert res.routes == (route,)
    assert res.levels == (level,)
    assert res.edges == spec.edges
    assert res.edge_inputs_per_sec > 0
    assert res.grid_steps > 0
    # per-panel plan stats, the warmup time, and the serving engine
    assert [(p["route"], p["level"]) for p in res.panels] == [
        (route, level)
    ] * 3
    assert not any(p["degraded"] for p in res.panels)
    assert res.warmup_seconds > 0
    assert not res.engine.ladder.events
    (plan,) = res.engine.plan_cache.plans()
    assert plan.width == 24 and plan.route == route
    assert plan.component_layers == 6
    assert plan.lower().compile() is not None


def test_challenge_driver_layered_and_failure():
    spec = rx.RadixNetSpec(64, 3)
    _, ref_cats = rx.radixnet_reference(
        spec, rx.radixnet_input_panel(64, 20, density=0.3, seed=5)
    )
    res = run_challenge(
        spec,
        n_inputs=20,
        panel_width=16,
        batch_align=8,
        seed=5,
    )
    assert res.routes == ("layered",)
    assert np.array_equal(res.categories, ref_cats)


# ---------------------------------------------------------------------
# The official challenge shapes (slow: interpret-mode kernels)
# ---------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize(
    "neurons,layers,density",
    [(1024, 120, 0.3), (4096, 120, 0.35)],
    ids=["1024x120", "4096x120"],
)
def test_conformance_challenge_config(neurons, layers, density):
    """Bit-level category agreement on GraphChallenge-scale stacks.

    The density per size keeps the un-clamped dynamics nondegenerate
    (see docs/benchmarks.md — this repo deliberately omits the official
    YMAX clamp): activations stay finite and the answer set is a strict,
    nonempty subset of the inputs.
    """
    spec = rx.RadixNetSpec(neurons, layers)
    y0 = rx.radixnet_input_panel(neurons, 32, density=density, seed=0)
    ref_y, ref_cats = rx.radixnet_reference(spec, y0)
    assert 0 < len(ref_cats) < 32  # nondegenerate ground truth
    assert np.isfinite(ref_y).all()

    ws, bs = rx.radixnet_weights(spec)
    yj = jnp.asarray(y0)
    layered = np.asarray(P.build_plan(ws, bs, yj.shape[1]).forward(yj))
    xla = np.asarray(dnn.dnn_forward(ws, bs, yj, fused=True))
    assert np.array_equal(rx.reference_categories(layered), ref_cats)
    assert np.array_equal(rx.reference_categories(xla), ref_cats)
    # layer-1 exactness: {0,1} inputs × the dyadic 1/16 weight make the
    # first layer bit-exact in f32 under ANY summation order
    conn0 = rx.radixnet_connectivity(neurons, 0)
    l1 = rx.reference_forward([conn0], [spec.bias], y0)
    l1_x = np.asarray(
        dnn.dnn_forward(ws[:1], bs[:1], yj, fused=True)
    )
    np.testing.assert_array_equal(l1, l1_x)


@pytest.mark.slow
@pytest.mark.parametrize(
    "route,level", [("layered", "layered")], ids=["auto-layered"]
)
def test_challenge_engine_routes_fused_tiled_past_vmem_budget(route, level):
    """A 16384-neuron stack serves on the layered route in the component
    layout and reproduces the ground-truth categories."""
    spec = rx.RadixNetSpec(16384, 6)
    assert spec.bias == -0.4
    y0 = rx.radixnet_input_panel(16384, 48, density=0.4, seed=2)
    _, ref_cats = rx.radixnet_reference(spec, y0)
    assert 0 < len(ref_cats) < 48
    res = run_challenge(
        spec, n_inputs=48, panel_width=24, batch_align=8,
        density=0.4, seed=2,
    )
    assert res.routes == (route,)
    assert res.levels == (level,)
    assert np.array_equal(res.categories, ref_cats)


# ---------------------------------------------------------------------
# 8-device sharded leg
# ---------------------------------------------------------------------

_SHARDED_BODY = textwrap.dedent(
    """
    import numpy as np
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.data import radixnet as rx
    from repro.launch.mesh import make_row_blocks_mesh
    from repro.serve import run_challenge

    assert len(jax.devices()) >= 8, jax.devices()
    spec = rx.RadixNetSpec(256, 7)
    y0 = rx.radixnet_input_panel(256, 40, density=0.3, seed=9)
    _, ref_cats = rx.radixnet_reference(spec, y0)
    assert 0 < len(ref_cats) < 40
    res = run_challenge(
        spec, n_inputs=40, panel_width=16, batch_align=8, seed=9,
        mesh=make_row_blocks_mesh(8),
    )
    assert res.routes == ("sharded",), res.routes
    assert res.levels == ("sharded",), res.levels
    assert np.array_equal(res.categories, ref_cats), (
        res.categories, ref_cats)
    print("challenge-sharded8 OK")
    """
)


@pytest.mark.slow
def test_challenge_sharded_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    body = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = '
        '"--xla_force_host_platform_device_count=8"\n' + _SHARDED_BODY
    )
    r = subprocess.run(
        [sys.executable, "-c", body],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "challenge-sharded8 OK" in r.stdout, r.stdout


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(the CI multi-device job sets it; tier-1 runs the subprocess "
    "variant instead)",
)
def test_challenge_sharded_inprocess(capsys):
    exec(compile(_SHARDED_BODY, "<challenge-sharded>", "exec"), {})
    assert "challenge-sharded8 OK" in capsys.readouterr().out
