"""The exchange share of a four-device trace: collective self time
inside the ``bench.query`` spans over busy time there, per device,
averaged."""

import pytest

from bench.lib import exchange
from bench.lib.measure import Trace


def _four_devices():
    # window [0, 100]; queries [0, 40] and [50, 90]
    spans = [("bench.query", 0, 40), ("bench.result", 40, 50),
             ("bench.query", 50, 90)]
    ops = [
        # a loop holding a sweep and an exchange; 10 of 60 busy ns
        [("%while.1 = (f32[4]) while(...)", 0, 30),
         ("%fusion.4 = f32[4] fusion(...)", 0, 20),
         ("%all-to-all.2 = f32[4] all-to-all(...)", 20, 30),
         ("%fusion.4 = f32[4] fusion(...)", 50, 80)],
        # async halves and a psum: 20 + 20 + 10 of 80
        [("all-to-all-start.3", 0, 20), ("all-to-all-done.3", 20, 40),
         ("fusion.1", 50, 80), ("all-reduce.7", 80, 90)],
        # waits at the barrier: 40 of 40; a collective outside the spans
        # (the result read) does not count
        [("collective-permute.1", 0, 20), ("all_to_all.2", 60, 80),
         ("all-reduce.9", 42, 48)],
        # no collective: 0 of 20
        [("gather.5", 5, 25)],
    ]
    return Trace((0, 100), ops, spans)


def test_collectives_by_name():
    assert exchange.is_collective("%all-to-all.2 = f32[4] all-to-all(x)")
    for name in ("all-reduce-start.1", "all-reduce-done.1", "all-gather",
                 "collective-permute-done.4", "all-to-all.3.1",
                 # as a TPU v5e trace names the exchange
                 "all_to_all.48", "all_reduce_start.2", "all_gather.7"):
        assert exchange.is_collective(name), name
    for name in ("fusion.1", "all-reduce-fusion.1", "gather.5",
                 "reduce.2", "%while.1 = (f32[4]) while(...)"):
        assert not exchange.is_collective(name), name


def test_share_per_device_averaged():
    share = exchange.collective_share_pct(_four_devices())
    per = [100 * 10 / 60, 100 * 50 / 80, 100 * 40 / 40, 0.0]
    assert share == pytest.approx(sum(per) / 4)


def test_nothing_to_read():
    tr = _four_devices()
    assert exchange.collective_share_pct(None) is None
    assert exchange.collective_share_pct(Trace(tr.window, [], tr.spans)) is None
    assert exchange.collective_share_pct(Trace(tr.window, tr.ops, [])) is None
    assert exchange.collective_share_pct(tr, "bench.absent") is None


def test_reader_reads_the_trace():
    import importlib.util
    import os
    import types

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", "exchange_share.teps.py")
    spec = importlib.util.spec_from_file_location("exchange_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = _four_devices()
    r = types.SimpleNamespace(counters={}, trace=tr, peaks={})
    assert mod.read(r) == exchange.collective_share_pct(tr)
    assert mod.read(types.SimpleNamespace(counters={}, trace=None,
                                          peaks={})) is None
