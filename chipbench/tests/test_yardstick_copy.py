"""The benchmark's copies of the RadiX-net connectivity, input generator
and reference agree bit for bit with the program's, so that a later change
to the program's generator shows here instead of moving the yardstick."""

import numpy as np
import pytest

from chipbench.yardstick import radixnet as yr

prx = pytest.importorskip("repro.data.radixnet")


@pytest.mark.parametrize(
    "neurons, layer",
    [(64, 0), (64, 1), (1024, 0), (1024, 1), (1024, 2), (16384, 0), (16384, 1), (16384, 2),
     (65536, 3)],
)
def test_connectivity_matches_program(neurons, layer):
    assert yr.num_phases(neurons) == prx.num_phases(neurons)
    np.testing.assert_array_equal(
        yr.radixnet_connectivity(neurons, layer), prx.radixnet_connectivity(neurons, layer)
    )


@pytest.mark.parametrize("neurons, n, density, seed", [
    (1024, 512, 0.3, 0), (16384, 64, 0.4, 2**31 + 11), (64, 7, 0.5, 123456789012),
])
def test_input_panel_matches_program(neurons, n, density, seed):
    a = yr.radixnet_input_panel(neurons, n, density=density, seed=seed)
    b = prx.radixnet_input_panel(neurons, n, density=density, seed=seed)
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("neurons, layers, n", [(64, 6, 40), (1024, 12, 300)])
def test_reference_matches_program(neurons, layers, n):
    spec = prx.RadixNetSpec(neurons, layers)
    y0 = yr.radixnet_input_panel(neurons, n, density=0.3, seed=5)
    ours = yr.stack_reference(neurons, layers, spec.bias, y0, chunk=16)
    theirs, cats = prx.radixnet_reference(spec, y0)
    np.testing.assert_array_equal(ours, theirs)
    np.testing.assert_array_equal(yr.reference_categories(ours), cats)
