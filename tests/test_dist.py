"""Distribution machinery: pipeline parallelism, compressed DP, logical
sharding rules, HLO analysis."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import logical_constraint, sharding_context

# The subprocess tests force the *host* platform (2 fake CPU devices), so
# pin the backend: on images that ship libtpu, an unset JAX_PLATFORMS
# makes the child probe for a TPU and sleep-retry until the timeout.
_SUBPROC_ENV = {
    "PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
}


def test_pipeline_two_stages_matches_sequential():
    """GPipe over 2 host devices == sequential layer apply (subprocess so
    the device count doesn't leak into other tests)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import make_pipeline_fn, bubble_fraction

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("pod",))
        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(2, 8, 8)) * 0.5, jnp.float32)
        xs = jnp.asarray(rng.normal(size=(4, 3, 8)), jnp.float32)  # M=4 mb

        fn = make_pipeline_fn(mesh, stage_fn, n_stages=2, n_micro=4,
                              axis="pod")
        ys = jax.jit(fn)(ws, xs)
        ref = jnp.stack([stage_fn(ws[1], stage_fn(ws[0], x)) for x in xs])
        assert np.allclose(np.asarray(ys), np.asarray(ref), atol=1e-5), (
            np.abs(np.asarray(ys) - np.asarray(ref)).max()
        )
        assert abs(bubble_fraction(4, 2) - 0.2) < 1e-9
        print("PIPELINE_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=_SUBPROC_ENV,
        cwd="/root/repo", timeout=600,
    )
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr


def test_compressed_psum_single_shard_roundtrip():
    """n_shards=1: compressed psum must reproduce the (quantized) mean and
    carry the residual in the error state."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.compressed_dp import (compressed_psum_mean,
                                              init_error_state)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("dp",))
        rng = np.random.default_rng(0)
        g_global = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)

        def body(g, e):
            m, e2 = compressed_psum_mean({"g": g[0]}, {"g": e[0]},
                                         "dp", 2)
            return m["g"][None], e2["g"][None]

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                           out_specs=(P("dp"), P("dp")), check_vma=False)
        mean, err = jax.jit(fn)(g_global, jnp.zeros_like(g_global))
        true_mean = np.asarray(g_global).mean(0)
        got = np.asarray(mean)
        # both shards agree and are close to the true mean (int8 quant)
        assert np.allclose(got[0], got[1], atol=1e-6)
        assert np.max(np.abs(got[0] - true_mean)) < 0.05
        # error feedback: residual + sent == contribution
        print("COMPRESSED_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=_SUBPROC_ENV,
        cwd="/root/repo", timeout=600,
    )
    assert "COMPRESSED_OK" in out.stdout, out.stdout + out.stderr


def test_logical_constraint_drops_indivisible_and_duplicate_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = {"batch": ("data",), "heads": "model", "seq": "model",
             "vocab": "model"}
    with sharding_context(mesh, rules):
        x = jnp.zeros((4, 6, 8))
        # heads (dim1) claims 'model'; seq (dim2... here named last) must
        # NOT claim it again
        y = logical_constraint(x, "batch", "heads", "seq")
        assert y.shape == x.shape
        # indivisible dim: silently unsharded, no error
        z = jnp.zeros((3, 5))
        logical_constraint(z, "batch", "heads")


def test_hlo_analysis_scan_awareness():
    from benchmarks.hlo_analysis import analyze_hlo

    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out.sum()

    ws = jnp.zeros((7, 16, 16))
    x = jnp.zeros((4, 16))
    text = jax.jit(f).lower(ws, x).compile().as_text()
    a = analyze_hlo(text)
    # 7 iterations x (2 * 4*16*16) flops
    expect = 7 * 2 * 4 * 16 * 16
    assert abs(a["flops"] - expect) / expect < 0.01, a["flops"]
    assert a["bytes_est"] > 7 * (16 * 16 * 4)   # weight reads per step
