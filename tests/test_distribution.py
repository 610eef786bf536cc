"""Sharding-rule resolver properties + distributed collectives semantics.

Multi-device semantics (embed_lookup vs plain gather, compressed psum
exactness) run in a SUBPROCESS with 8 fake host devices so the main test
process keeps its single-device view (dry-run contract)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distribution.sharding import (
    ShardingRules,
    _logical_axes,
    _resolve_spec,
    param_pspecs,
)
from repro.models.model import Model


class _FakeMesh:
    def __init__(self, shape: dict):
        self.shape = shape


@given(
    dim=st.integers(1, 4096),
    axis=st.sampled_from([2, 3, 16]),
)
@settings(max_examples=40, deadline=None)
def test_resolver_divisibility_fallback(dim, axis):
    mesh = _FakeMesh({"data": axis, "model": 16})
    spec = _resolve_spec(("fsdp",), (dim,), mesh, ShardingRules())
    got = spec[0] if len(spec) else None
    if dim % axis == 0:
        assert got == "data"
    else:
        assert got is None


def test_resolver_never_reuses_axis():
    mesh = _FakeMesh({"data": 4, "model": 4})
    spec = _resolve_spec(("fsdp", "fsdp"), (16, 16), mesh, ShardingRules())
    axes = [s for s in tuple(spec) if s is not None]
    assert len(axes) <= 1  # second use of the same axis must drop


def test_param_pspecs_cover_all_leaves():
    cfg = get_config("jamba-v0.1-52b").scaled_down()
    model = Model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    mesh = _FakeMesh({"data": 2, "model": 2})
    specs = param_pspecs(cfg, shapes, mesh, ShardingRules())
    s_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    p_leaves = jax.tree.leaves(shapes)
    assert len(s_leaves) == len(p_leaves)
    for spec, leaf in zip(s_leaves, p_leaves):
        assert len(tuple(spec)) <= leaf.ndim


def test_period_leading_axis_never_sharded():
    names = ["stack", "period", "0", "ffn", "w_in"]
    axes = _logical_axes(names, 3)  # stacked (L, d, ff)
    assert axes[0] is None


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distribution import sharding as sh
    from repro.distribution.collectives import compressed_psum_mean
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(4, 2)

    # --- embed_lookup == table[ids] under sharding -----------------------
    key = jax.random.key(0)
    table = jax.random.normal(key, (64, 16))
    ids = jax.random.randint(jax.random.key(1), (8, 12), 0, 64)
    with mesh, sh.activate(mesh):
        f = jax.jit(lambda t, i: sh.embed_lookup(t, i))
        out = f(
            jax.device_put(table, NamedSharding(mesh, P("data", "model"))),
            jax.device_put(ids, NamedSharding(mesh, P("data", None))),
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(table)[np.asarray(ids)], rtol=1e-6)
    print("embed_lookup OK")

    # --- compressed psum: int8 error feedback ----------------------------
    gmesh = make_local_mesh(8)
    x = jax.random.normal(jax.random.key(2), (8, 256))

    def body(xl, el):
        m, e = compressed_psum_mean(xl[0], "data", el[0])
        return m[None], e[None]

    with gmesh:
        mfn = jax.shard_map(
            body, mesh=gmesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
        )
        err = jnp.zeros_like(x)
        m1, err = mfn(x, err)
        exact = jnp.mean(x, axis=0)
        q_err1 = float(jnp.max(jnp.abs(m1[0] - exact)))
        # quantization error bounded by the int8 step size
        step = float(jnp.max(jnp.abs(x)) / 127.0)
        assert q_err1 <= step + 1e-6, (q_err1, step)
        # error feedback: running mean over repeats converges
        acc = m1[0]
        for rep in range(24):
            m, err = mfn(x, err)
            acc = acc + m[0]
        avg = acc / 25.0
        drift = float(jnp.max(jnp.abs(avg - exact)))
        assert drift < step * 0.2, (drift, step)
    print("compressed_psum OK")
    """
)


@pytest.mark.slow
def test_multidevice_semantics_subprocess():
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
    assert "embed_lookup OK" in r.stdout
    assert "compressed_psum OK" in r.stdout
