"""Ahead-of-time compiles for a TPU v5e, made without the chip
(DESIGN.md §14).

The TPU compiler is installed with JAX and compiles for a described,
unattached ``v5e:2x2`` topology.  That refuses what interpret mode
cannot: Pallas primitives Mosaic does not lower, unaligned tiles, too
much scoped VMEM.  Covered here:

* the four tick kernels at DF-1056 shapes (M = 5024 compacted enqueues,
  N = 33,856 packet slots, F = 1,056 flows, NP = 3,960 ports, P = 64
  paths), solo and vmapped over 3 lanes as ``run_batch`` runs them —
  each program must hold a ``tpu_custom_call`` (a real Mosaic kernel,
  not the interpreter);
* the micro cell's batched runner, whose per-flow sums take the one-hot
  GEMM form: every f32 dot in it must run at ``Precision.HIGHEST``
  (the TPU's default bf16 product is exact only up to 256).

The topology is described inside a module fixture, which skips where it
cannot be described; nothing here touches the TPU library at import.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

M, N, F, NP, P = 5024, 33856, 1056, 3960, 64
LANES = 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernel wrappers off interpret mode, as on a TPU."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


def _red_ecn(eport, rank, enq, unif, q_tail, t):
    return ops.red_ecn(eport, rank, enq, unif, q_tail, t, qsize=88,
                       kmin=17.6, kmax=70.4, n_ports=NP)


KERNELS = {
    # name: (fn, [(shape, dtype)] of one lane's arguments)
    "tick_rank": (lambda port: ops.tick_rank(port, n_ports=NP),
                  [((M,), jnp.int32)]),
    "red_ecn": (_red_ecn,
                [((M,), jnp.int32), ((M,), jnp.int32), ((M,), jnp.bool_),
                 ((M,), jnp.float32), ((NP,), jnp.int32), ((), jnp.int32)]),
    "flow_agg": (lambda rows, pflow: ops.flow_agg(rows, pflow, n_flows=F),
                 [((6, N), jnp.int32), ((N,), jnp.int32)]),
    "spritz_select": (
        lambda w, u, front, cnt: ops.spritz_select(w, u, front, cnt,
                                                   explore_threshold=44),
        [((F, P), jnp.float32), ((F,), jnp.float32), ((F,), jnp.int32),
         ((F,), jnp.int32)]),
}


@pytest.mark.parametrize("lanes", [None, LANES], ids=["solo", "vmapped"])
@pytest.mark.parametrize("name", list(KERNELS))
def test_tick_kernel_compiles_for_v5e(name, lanes, one_chip, mosaic):
    fn, shapes = KERNELS[name]
    if lanes:
        fn = jax.vmap(fn)
        shapes = [((lanes,) + s, d) for s, d in shapes]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


_F32_DOT = re.compile(r"= f32\[[^\]]*\][^=]*\b(?:convolution|dot)\(")


def test_micro_runner_flow_sums_run_at_highest_precision(one_chip):
    from repro.exp import matrix
    from repro.exp.workloads import build_workload, make_topology
    from repro.net.sim import build as B
    from repro.net.sim import engine as E
    from repro.net.sim.types import SPRAY_W

    cell = matrix.CELLS["micro.dragonfly.adversarial.smoke"]
    topo = make_topology(cell.topology, cell.scale)
    spec = B.build_spec(topo, build_workload(cell, topo).flows, SPRAY_W,
                        n_ticks=cell.n_ticks, **dict(cell.spec_kw))
    assert spec.n_pkt * spec.n_flows <= E._ONEHOT_CELLS  # GEMM form
    _, _, n_shard, args = E._batch_inputs(spec, list(cell.schemes),
                                          list(cell.seeds), shard=False)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), args)
    runner = E._runner(spec, dense=False, batched=True, shard=n_shard)
    text = runner.lower(*shapes).compile().as_text()
    dots = [line for line in text.splitlines() if _F32_DOT.search(line)]
    loose = [d for d in dots if "operand_precision={highest,highest}" not in d]
    assert not loose, loose[:3]
