"""chip_smoke.py on the CPU: its phases at a tiny scale (the chip run's
rehearsal), its host references, and its refusal to run without a TPU."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

pytest.importorskip("scipy")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_pass_on_cpu(smoke, tmp_path, capsys):
    import jax

    args = smoke.parse_args(["--scale", "8", "--out", str(tmp_path)])
    smoke.run_one_chip(args, smoke.Clock(jax))
    out = capsys.readouterr().out
    checks = [ln for ln in out.splitlines() if ln.startswith("check ")]
    assert len(checks) == 17 and all(": ok" in ln for ln in checks), out
    for phase in ("1-ingest", "2-queries", "3-update", "4-durable",
                  "5-pallas", "6-sum"):
        assert f"phase {phase}: wall_s=" in out
    assert not (tmp_path / "snapshot").exists()


def test_references_agree_with_plain_loops(smoke):
    from collections import deque

    from repro.core.generators import erdos_renyi

    n = 300
    src, dst = erdos_renyi(n, avg_degree=1.5, seed=3)   # many components
    g = smoke._csr(src, dst, np.ones(src.size, np.float32), n)
    adj = [[] for _ in range(n)]
    for u, v in zip(src, dst):
        adj[u].append(int(v))
    depth = np.full(n, np.inf)
    label = np.full(n, -1)
    for root in range(n):
        if label[root] >= 0:
            continue
        label[root] = root
        hops = {root: 0}
        q = deque([root])
        while q:
            u = q.popleft()
            for v in adj[u]:
                if label[v] < 0:
                    label[v] = root
                    hops[v] = hops[u] + 1
                    q.append(v)
        if root == 0:
            for v, h in hops.items():
                depth[v] = h
    assert np.array_equal(smoke.ref_bfs(g, 0), depth)
    comp = smoke.ref_cc(g)
    assert np.unique(label).size > 1
    assert smoke.same_partition(comp, label)
    assert smoke.same_partition(comp, comp * 7 + 1)
    assert not smoke.same_partition(comp, np.zeros_like(comp))


def test_ppr_comparison_rejects_a_moved_rank(smoke):
    from types import SimpleNamespace

    rng = np.random.default_rng(0)
    rank = (rng.random(1000) * 1e-3).astype(np.float32)
    resid = (rng.random(1000) * 1e-4).astype(np.float32)
    a = SimpleNamespace(values=rank, extra={"residual": resid})
    assert smoke.ppr_close(a, a)[0]
    moved = rank.copy()
    moved[7] += 1e-3                     # far below the leftover residual
    assert resid.sum() > 1e-3
    b = SimpleNamespace(values=moved, extra={"residual": resid})
    assert not smoke.ppr_close(a, b)[0]
    c = SimpleNamespace(values=rank, extra={"residual": resid[::-1]})
    assert not smoke.ppr_close(a, c)[0]


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr
