"""The four-chip query cell (``graph500-s18-spmd``) end to end on four
virtual CPU devices at scale 10: correct against scipy, no compile in
the window, its metrics reported.  In a subprocess, since the device
count is fixed when JAX starts."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CELL = ("graph500-s18-spmd", "query_closed")

SCRIPT = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path[:0] = [{ROOT!r}, os.path.join({ROOT!r}, "src"),
                    os.path.join({ROOT!r}, "bench", "tests")]
    from bench import run as R
    from bench_cells import run_cell

    real = R.load_json
    R.load_json = lambda p: ({{"cpu": {{"hbm_bytes_per_s": 1e11}}}}
                             if p.endswith("peaks.json") else real(p))
    for trace in (0, 1):
        res = run_cell({CELL!r}, seconds=2.0, scale=10, trace=trace)
        print("RESULT " + json.dumps(res), flush=True)
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    results = [json.loads(line.split(" ", 1)[1])
               for line in out.stdout.splitlines()
               if line.startswith("RESULT ")]
    windows = [line for line in out.stderr.splitlines()
               if line.startswith("window: ")]
    assert len(results) == len(windows) == 2, out.stderr[-4000:]
    return results, windows


def test_spmd_cell_is_correct_on_four_devices(runs):
    (res, traced), _ = runs
    for r in (res, traced):
        assert r["correct"], r["checks"]
        assert r["attempted"] > 2 and r["failed"] == 0
        assert r["device"]["count"] == 4
    assert set(res["metrics"]) == {"query_teps", "setup_s"}
    assert res["metrics"]["query_teps"]["value"] > 0


def test_spmd_cell_window_compiles_nothing(runs):
    _, windows = runs
    for line in windows:
        fields = dict(kv.split("=", 1) for kv in line.split()[1:])
        assert fields["compiles"] == "0", line


def test_spmd_cell_traced_reports_its_layers(runs):
    (_, traced), _ = runs
    assert traced["metrics"]["subiters_per_query.teps"]["value"] > 0
    # the CPU has no device plane: nothing to read for the device metrics
    for name in ("idle_share.teps", "exchange_share.teps",
                 "relax_hbm_share.teps"):
        assert name not in traced["metrics"]
