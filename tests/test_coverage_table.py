"""Table 1 of the paper: which suite kernels COX's hierarchical
collapsing runs, and which the flat (block-wide loop) collapsing could.

Every runnable row of ``benchmarks.kernels_suite.KERNELS`` launches with
``collapse='hier'`` and must agree with the per-thread oracle
(``repro.core.oracle.run_grid``) and pass the row's own check.  The
rows COX cannot run (grid sync inside a loop, multi-grid sync, dynamic
cooperative groups) carry no kernel and say why.  The totals are the
paper's: COX 28/31 (90%), flat 16/31.
"""
import numpy as np
import pytest

from benchmarks.kernels_suite import KERNELS
from repro.core.flat import supports_flat
from repro.core.oracle import run_grid


@pytest.mark.parametrize("sk", KERNELS, ids=lambda sk: sk.name)
def test_table1_row(sk):
    if sk.kernel is None:
        assert sk.unsupported_reason, sk.name
        return
    args = sk.make_args()
    out = sk.kernel.launch(grid=sk.grid, block=sk.block, args=args,
                           collapse="hier")
    ref = run_grid(sk.kernel.ir, grid=sk.grid, block=sk.block, args=args)
    assert ref, sk.name
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32), np.asarray(ref[k], np.float32),
            rtol=1e-4, atol=1e-4, err_msg=f"{sk.name}.{k}")
    if sk.check is not None:
        assert sk.check(out), f"{sk.name}: check failed"


def test_table1_totals():
    runnable = [sk for sk in KERNELS if sk.kernel is not None]
    n_flat = sum(supports_flat(sk.kernel.ir) for sk in runnable)
    assert (len(KERNELS), len(runnable), n_flat) == (31, 28, 16)
