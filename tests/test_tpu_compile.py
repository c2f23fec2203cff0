"""The edge_relax paths compiled for a described TPU v5e chip.

Nothing runs: each test lowers one relaxation path at per-cell widths
(Np = 65,536 vertices, E = 1,048,576 edges) and compiles it with the TPU
compiler for a ``v5e:2x2`` topology, which refuses what the chip would
refuse (tile-misaligned blocks, unsupported Mosaic ops, programs too big
for HBM).  The topology is described inside a module fixture, never at
import time: one process at a time may load the TPU library.
"""

import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import relax as relax_mod
from repro.core.programs import PROGRAMS
from repro.kernels.edge_relax.kernel import (
    edge_relax_blocks,
    edge_relax_push_blocks,
)
from repro.kernels.edge_relax.ops import edge_relax, edge_relax_push

NP = 65_536
E = 1_048_576
BLOCK = 128
CAP = 64            # push compaction bucket


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    old = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    if old is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _prog(name):
    return PROGRAMS[name].factory(
        **({"source": 0} if name in ("sssp", "bfs", "ppr") else {}))


def _cell_args(prog, sharding):
    """Shape-only arguments of one cell's sweep: (vstate, senders, gid,
    key, src, weight, dst_gid)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=sharding)

    def init(gid, node_ok, out_degree):
        return prog.init(types.SimpleNamespace(
            gid=gid, node_ok=node_ok, out_degree=out_degree))

    vs, _ = jax.eval_shape(init, jax.ShapeDtypeStruct((1, NP), jnp.int32),
                           jax.ShapeDtypeStruct((1, NP), bool),
                           jax.ShapeDtypeStruct((1, NP), jnp.int32))
    vstate = jax.tree_util.tree_map(lambda a: sds((NP,), a.dtype), vs)
    return (vstate, sds((NP,), bool), sds((NP,), jnp.int32),
            sds((E,), jnp.int32), sds((E,), jnp.int32),
            sds((E,), jnp.float32), sds((E,), jnp.int32))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _push_view(key, src, weight, dst_gid):
    # shapes only: the push streams share the dense streams' widths
    return {"push_src": src, "push_key": key, "push_weight": weight,
            "push_dst_gid": dst_gid, "push_pos": key}


@pytest.mark.parametrize("name", ["sssp", "cc", "pagerank"])
def test_xla_pull_compiles(one_chip, name):
    """Dense pull sweep on backend='xla': the blocked segmented scan for
    every program, with the run layout computed once outside the sweep
    (as the engines pass it) — on a delta-free stream it holds no
    scatter."""
    prog = _prog(name)
    run_end = jax.ShapeDtypeStruct((NP,), jnp.int32, sharding=one_chip)

    def f(vs, se, gid, key, src, w, dg, run_end):
        return edge_relax(prog, vs, se, gid, key, src, w, dg, n_keys=NP,
                          block_e=BLOCK, backend="xla", run_end=run_end)

    text = _compile(f, *_cell_args(prog, one_chip), run_end)
    assert "tpu_custom_call" not in text
    assert "scatter" not in text


@pytest.mark.parametrize("name", ["sssp", "pagerank"])
def test_xla_push_compiles(one_chip, name):
    """Frontier-compacted push sweep on backend='xla' (the unsorted
    segment combine for min, the dense-stream reconstruction + scan for
    sum)."""
    prog = _prog(name)

    def f(vs, se, gid, key, src, w, dg):
        return edge_relax_push(prog, vs, se, gid,
                               _push_view(key, src, w, dg), key, n_keys=NP,
                               block_e=BLOCK, cap=CAP, backend="xla")

    _compile(f, *_cell_args(prog, one_chip))


@pytest.mark.parametrize("name", ["sssp", "bfs", "cc"])
def test_pallas_block_kernel_compiles(one_chip, name):
    """The Pallas block kernel lowers through Mosaic (a tpu_custom_call
    in the compiled module), with and without the argmin payload."""
    prog = _prog(name)

    def f(vs, se, gid, key, src, w, dg):
        return edge_relax_blocks(prog, vs, se, gid, key, src, w, dg, BLOCK)

    assert "tpu_custom_call" in _compile(f, *_cell_args(prog, one_chip))


@pytest.mark.parametrize("name", ["sssp", "cc"])
def test_pallas_push_kernel_compiles(one_chip, name):
    prog = _prog(name)
    args = _cell_args(prog, one_chip)
    idx = jax.ShapeDtypeStruct((CAP,), jnp.int32, sharding=one_chip)

    def f(vs, se, gid, key, src, w, dg, idx):
        return edge_relax_push_blocks(prog, vs, se, gid,
                                      _push_view(key, src, w, dg), idx,
                                      BLOCK)

    assert "tpu_custom_call" in _compile(f, *args, idx)


def test_pallas_sum_program_refused_on_tpu(monkeypatch):
    """The scan kernel has no TPU lowering, so a sum program on
    backend='pallas' raises on a TPU instead of silently taking XLA."""
    monkeypatch.setattr(relax_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="sum-combine"):
        relax_mod.make_relax(_prog("pagerank"), 1, NP, BLOCK,
                             backend="pallas")
    # min programs keep the compiled block kernel
    relax_mod.make_relax(_prog("sssp"), 1, NP, BLOCK, backend="pallas")
