"""The byte count behind relax_hbm_share."""

import pytest

from bench.lib import work


def test_sweep_bytes():
    assert work.sweep_bytes(0, 0) == 0
    assert work.sweep_bytes(1, 0) == 16
    assert work.sweep_bytes(0, 1) == 8
    # scale 16 Graph500: 1,818,572 directed edges, 65,536 vertices
    assert work.sweep_bytes(1_818_572, 65_536) == 29_621_440


def test_hbm_share():
    # 10 sweeps of 819 MB each in 10 s on an 819 GB/s chip: 0.1 %
    share = work.hbm_share_pct(10, 819e6 / 16, 0, 10.0, 819e9)
    assert share == pytest.approx(0.1)
    assert work.hbm_share_pct(0, 10, 10, 1.0, 1e9) is None
    assert work.hbm_share_pct(10, 10, 10, 0.0, 1e9) is None
