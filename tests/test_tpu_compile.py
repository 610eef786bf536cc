"""Compiles for a described TPU v5e: the kernels the engine routes to.

The TPU compiler ships with JAX and compiles for a chip that is
described, not attached, so these tests need no accelerator. Each one
compiles a Pallas kernel through the wrapper the plan layer calls
(``repro.kernels.ops``, ``interpret=False``) at a GraphChallenge shape
its route admits, or shows that a route guard keeps a stack off a kernel
the compiler refuses. Nothing runs: a compile proves neither results nor
speed. The topology is described inside a module fixture, never at
import, so only the worker that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.plan as P
from repro.data import radixnet as rx
from repro.kernels import bsr_spmm as ell_kernel
from repro.kernels import fused_mlp
from repro.kernels import ops as kernel_ops
from repro.plan.layout import preferred_layout
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

BS = 16  # the RadiX-net block size
MBPR = 32  # RadiX-net butterfly phases store 32 blocks per block-row
WIDTH = 512  # the challenge panel width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep these compiles out of it
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _s(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _ell(one_chip, neurons, layers=None):
    """An ELL weight (or an L-stack of them) as shapes on the chip."""
    nrb = neurons // BS
    lead = () if layers is None else (layers,)
    return BlockSparseMatrix(
        _s(one_chip, lead + (nrb, MBPR, BS, BS)),
        _s(one_chip, lead + (nrb, MBPR), jnp.int32),
        _s(one_chip, lead + (nrb, MBPR), jnp.int32),
        (neurons, neurons),
        (BS, BS),
    )


def _csr(one_chip, neurons, total_blocks):
    return BlockCSRMatrix(
        _s(one_chip, (total_blocks, BS, BS)),
        _s(one_chip, (neurons // BS + 1,), jnp.int32),
        _s(one_chip, (total_blocks,), jnp.int32),
        _s(one_chip, (total_blocks,), jnp.int32),
        _s(one_chip, (total_blocks,), jnp.int32),
        (neurons, neurons),
        (BS, BS),
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_args(one_chip, neurons, layers):
    return (
        _ell(one_chip, neurons, layers),
        _s(one_chip, (layers, neurons)),
        _s(one_chip, (neurons, WIDTH)),
    )


# ---------------------------------------------------------------------
# Every kernel compiles at a shape its route admits
# ---------------------------------------------------------------------


def test_bcsr_spmm_compiles_at_16384(one_chip):
    w = _csr(one_chip, 16384, 16384 // BS * MBPR)
    assert P.layer_path(w, differentiable=False) == "kernel-bcsr"
    text = _compiled_text(
        lambda w, y, b: kernel_ops.bcsr_spmm(
            w, y, b, fuse_bias_relu=True, interpret=False
        ),
        w,
        _s(one_chip, (16384, WIDTH)),
        _s(one_chip, (16384,)),
    )
    assert "tpu_custom_call" in text


def test_bsr_spmm_compiles_at_4096(one_chip):
    w = _ell(one_chip, 4096)
    assert ell_kernel.smem_fits(w)
    assert P.layer_path(w, differentiable=False) == "kernel-ell"
    text = _compiled_text(
        lambda w, y, b: kernel_ops.bsr_spmm(
            w, y, b, fuse_bias_relu=True, interpret=False
        ),
        w,
        _s(one_chip, (4096, WIDTH)),
        _s(one_chip, (4096,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "neurons,panel_dtype",
    [(4096, None), (8192, "bfloat16")],
    ids=["4096-f32", "8192-bf16"],
)
def test_fused_mlp_forward_compiles_at_vmem_budget(
    one_chip, neurons, panel_dtype
):
    """The largest resident panels: 4096 f32 and 8192 bf16 both fill
    ``VMEM_SOFT_LIMIT_BYTES`` exactly (six stripes, as allocated)."""
    assert (
        fused_mlp.fused_mlp_vmem_bytes(neurons, panel_dtype=panel_dtype)
        == fused_mlp.VMEM_SOFT_LIMIT_BYTES
    )
    layers = [_ell(one_chip, neurons)] * 2
    assert P.fused_route(layers, panel_dtype=panel_dtype) == P.ROUTE_FUSED
    text = _compiled_text(
        lambda w, b, y: kernel_ops.fused_mlp_forward(
            w, b, y, interpret=False, panel_dtype=panel_dtype
        ),
        *_fused_args(one_chip, neurons, 2),
    )
    assert "tpu_custom_call" in text


def test_fused_mlp_tiled_forward_compiles_at_16384(one_chip):
    """The challenge bench cell (16384 × 6): past the VMEM budget, and
    its slot table fits SMEM."""
    layers = [_ell(one_chip, 16384)] * 6
    assert P.fused_route(layers) == P.ROUTE_FUSED_TILED
    text = _compiled_text(
        lambda w, b, y: kernel_ops.fused_mlp_tiled_forward(
            w, b, y, interpret=False
        ),
        *_fused_args(one_chip, 16384, 6),
    )
    assert "tpu_custom_call" in text


def test_semiring_matmul_compiles(one_chip):
    w = _s(one_chip, (1024, 1024))
    assert P.layer_path(w, differentiable=False) == "kernel-dense"
    text = _compiled_text(
        lambda a, y, b: kernel_ops.semiring_matmul(
            a, y, b, fuse_bias_relu=True, interpret=False
        ),
        w,
        _s(one_chip, (1024, WIDTH)),
        _s(one_chip, (1024,)),
    )
    assert "tpu_custom_call" in text


def _radix_plan_compiles(one_chip, monkeypatch, neurons, blocks, grid_steps):
    """The engine's plan of the official ``neurons`` × 120 stack routes it
    layered on block-CSR kernels in the component layout, each distinct
    phase matrix re-laid once as ``blocks`` blocks of 1024×32 (32 whole
    32×32 components a block-row, one entry per row a block); the layer
    kernel at the challenge width and the whole executable, gathers
    included, compile for one chip."""
    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(neurons, 120))
    assert P.fused_route(ws) is None
    plan = P.build_plan(ws, bs, WIDTH)
    assert plan.route == P.ROUTE_LAYERED
    assert {lp.path for lp in plan.layers} == {"kernel-bcsr"}
    assert plan.component_layers == 120
    assert plan.grid_steps == grid_steps
    distinct = list({id(w): w for w in plan.weights}.values())
    assert len(distinct) == plan.weight_args == rx.num_phases(neurons)
    assert all(
        w.total_blocks == blocks and w.block_shape == (1024, 32) for w in distinct
    )
    fn = jax.jit(
        lambda w, y, b: kernel_ops.bcsr_spmm(
            w, y, b, fuse_bias_relu=True, interpret=False
        )
    )
    shapes = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), distinct[-1])
    text = (
        fn.lower(
            shapes,
            _s(one_chip, (neurons, WIDTH)),
            _s(one_chip, (neurons,)),
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
    # the executable calls the kernels with the backend's own choice
    monkeypatch.setattr(kernel_ops, "auto_interpret", lambda: False)
    bound = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), plan._bound())
    y = _s(one_chip, (neurons, WIDTH))
    text = plan._fn.lower(*bound, y).compile().as_text()
    jax.clear_caches()  # drop the kernels traced for the chip
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 120
    assert "gather" in text


def test_radix_16384x120_plan_layer_kernels_compile(one_chip, monkeypatch):
    """16384 × 120: three phases of 512 components (the fused slot table
    and the ELL prefetch tables overflow SMEM)."""
    _radix_plan_compiles(one_chip, monkeypatch, 16384, 512, 245_760)


def test_radix_65536x120_plan_layer_kernels_compile(one_chip, monkeypatch):
    """65536 × 120, the largest official stack: four phases (radix-32
    butterflies at strides 1, 32 and 1024, then a mixed radix-2 ⊗
    radix-16 phase at stride 32768) of 2,048 components, 64 block-rows
    of 32 blocks each. Passed once per layer, its 120 weights would need
    30 GB of arguments; passed once per phase, 1.2 GB."""
    _radix_plan_compiles(one_chip, monkeypatch, 65536, 2048, 983_040)


# ---------------------------------------------------------------------
# Guards: a stack the compiler refuses is routed elsewhere
# ---------------------------------------------------------------------


def test_fused_slot_table_guard_4096x120(one_chip):
    """4096 × 120 fits VMEM, but its fused slot table (3.75 MiB) does not
    fit the 1 MiB SMEM: the compiler refuses the fused kernel, so the
    route and the plan go layered."""
    layers = [_ell(one_chip, 4096)] * 120
    assert fused_mlp.fused_mlp_eligible(layers[0])  # VMEM alone would admit it
    assert P.fused_route(layers) is None
    with pytest.raises(Exception, match="(?i)smem"):
        _compiled_text(
            lambda w, b, y: kernel_ops.fused_mlp_forward(
                w, b, y, interpret=False
            ),
            *_fused_args(one_chip, 4096, 120),
        )
    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(4096, 120))
    plan = P.build_plan(ws, bs, WIDTH)
    assert plan.route == P.ROUTE_LAYERED
    assert not plan.is_fused_route


@pytest.mark.parametrize(
    "layers,fits", [(7, True), (8, False)], ids=["7-fits", "8-refused"]
)
def test_fused_slot_table_guard_is_exact(one_chip, layers, fits):
    """At 16384 neurons a layer adds 128 KiB of slot table: seven layers
    fit SMEM and compile on the tiled route; the eighth tips the stack
    off the fused routes, and the compiler refuses it."""
    stack = [_ell(one_chip, 16384)] * layers
    args = _fused_args(one_chip, 16384, layers)

    def tiled(w, b, y):
        return kernel_ops.fused_mlp_tiled_forward(w, b, y, interpret=False)

    if fits:
        assert P.fused_route(stack) == P.ROUTE_FUSED_TILED
        assert "tpu_custom_call" in _compiled_text(tiled, *args)
    else:
        assert P.fused_route(stack) is None
        with pytest.raises(Exception, match="(?i)smem"):
            _compiled_text(tiled, *args)


@pytest.mark.parametrize(
    "neurons,fits", [(16128, True), (16384, False)], ids=["16128", "16384"]
)
def test_ell_prefetch_guard(one_chip, neurons, fits):
    """The ELL kernel's (nrb, 32) column table and mask pad to 128 lanes
    in SMEM, 1 KiB per block-row: 1008 block-rows (16128 neurons) fit,
    1024 (16384) do not, and such a layer is re-laid to block-CSR."""
    w = _ell(one_chip, neurons)
    assert ell_kernel.smem_fits(w) is fits

    def ell(w, y):
        return kernel_ops.bsr_spmm(w, y, interpret=False)

    y = _s(one_chip, (neurons, WIDTH))
    if fits:
        assert "tpu_custom_call" in _compiled_text(ell, w, y)
        return
    with pytest.raises(Exception, match="(?i)smem"):
        _compiled_text(ell, w, y)
    # a real 16384-neuron ELL layer with no pad waste (the stride-32
    # butterfly phase fills all 32 slots) still leaves ELL
    ws, _ = rx.radixnet_weights(rx.RadixNetSpec(16384, 2))
    w16k = ws[1]
    assert bool(w16k.block_mask.all())
    assert preferred_layout(w16k) == "bcsr"
    plan = P.build_plan(
        [w16k], [jnp.zeros((16384,))], WIDTH, use_resident=False
    )
    assert [lp.path for lp in plan.layers] == ["kernel-bcsr"]


@pytest.mark.parametrize(
    "total_blocks,fits",
    [(86016, True), (131072, False)],
    ids=["86016", "131072"],
)
def test_bcsr_prefetch_guard(one_chip, total_blocks, fits):
    """The block-CSR kernel's three flat (T,) tables: 86016 stored blocks
    fit SMEM; 131072 (a 65536-neuron RadiX-net layer in 16×16 blocks)
    do not, and the plan refuses to route such a layer rather than hand
    the compiler a kernel it rejects. The plan's component layout stores
    that layer as 2,048 blocks of 1024×32 instead."""
    w = _csr(one_chip, 65536, total_blocks)
    y = _s(one_chip, (65536, WIDTH))

    def csr(w, y):
        return kernel_ops.bcsr_spmm(w, y, interpret=False)

    if fits:
        assert P.layer_path(w, differentiable=False) == "kernel-bcsr"
        assert "tpu_custom_call" in _compiled_text(csr, w, y)
        return
    with pytest.raises(ValueError, match="SMEM"):
        P.layer_path(w, differentiable=False)
    with pytest.raises(Exception, match="(?i)smem"):
        _compiled_text(csr, w, y)
