"""The query loop against scipy at scale 8 on the CPU, and the faults
that ``correct`` has to catch."""

import numpy as np
import pytest

from bench_cells import QUERY, SEED, load, run_cell

from bench import control
from bench import run as R


def test_query_cell_is_correct():
    res = run_cell(QUERY)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"query_teps", "setup_s"}
    assert res["metrics"]["query_teps"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_query_cell_traced_reports_its_layers(monkeypatch):
    real = R.load_json
    monkeypatch.setattr(R, "load_json", lambda p: (
        {"cpu": {"hbm_bytes_per_s": 1e11}} if p.endswith("peaks.json")
        else real(p)))
    res = run_cell(QUERY, trace=1)
    assert res["correct"]
    assert res["metrics"]["subiters_per_query.teps"]["value"] > 0
    # the CPU has no device plane: nothing to read for the device metrics
    assert "idle_share.teps" not in res["metrics"]
    assert "relax_hbm_share.teps" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_device_kind_fails_a_traced_run():
    with pytest.raises(SystemExit, match="no peaks"):
        run_cell(QUERY, trace=1)


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.core.session import DiffusionSession, Result

    real = DiffusionSession.query

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        v = np.array(res.values, copy=True)
        v[np.flatnonzero(np.isfinite(v))[-1]] += 1
        return Result(v, res.stats, res.extra)

    monkeypatch.setattr(DiffusionSession, "query", altered)
    res = run_cell(QUERY)
    assert not res["correct"] and res["failed"] > 0


def test_control_in_the_program_place_is_not_correct(monkeypatch):
    """The bfloat16 control answers every sssp query of the window in the
    program's place, and the harness's ``correct`` comes out false."""
    from repro.core.session import DiffusionSession, Result

    from bench.lib import refs

    real_session, real_query = R.Context.session, DiffusionSession.query
    seen = {}

    def session(self, g):
        seen["g"] = g
        return real_session(self, g)

    def lowp(self, prog, *a, source, **kw):
        res = real_query(self, prog, *a, source=source, **kw)
        if prog != "sssp":
            return res
        g = seen["g"]
        return Result(refs.sssp_lowp(g.src, g.dst, g.w, g.n, source),
                      res.stats, res.extra)

    monkeypatch.setattr(R.Context, "session", session)
    monkeypatch.setattr(DiffusionSession, "query", lowp)
    res = run_cell(QUERY, scale=10)
    limit = load(*QUERY)[0]["limits"]["sssp_rel_gap"]
    assert not res["correct"] and res["failed"] > 0
    assert res["checks"]["sssp_rel_gap"]["value"] > 3 * limit


def test_control_fails_the_limit_and_the_program_passes():
    cfg, mix = load(*QUERY)
    cfg = dict(cfg, generator=dict(cfg["generator"], scale=10))
    limit = cfg["limits"]["sssp_rel_gap"]
    assert control.control_gap(cfg, mix, SEED, 2) > 3 * limit
    res = run_cell(QUERY, scale=10)
    assert res["checks"]["sssp_rel_gap"]["value"] < limit / 3
