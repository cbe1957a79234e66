"""Compile-only checks of the main-path kernels for a TPU v5e chip.

Each test lowers and compiles one Pallas kernel at a real width against
the described (not attached) ``v5e:2x2`` topology, so whatever Mosaic
refuses — a slice off the (8, 128) tiling, too much VMEM or SMEM — fails
here without a chip; one compiles the four-chip sharded reorder over the
whole host and checks what it holds on each chip.  Nothing runs.  The topology is described inside a
module fixture (never at import: only one process may load the TPU
library), and the tests skip when it cannot be described.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.bcsr_spmv import (block_ell_spmv_batched,
                                     block_ell_spmv_window)
from repro.kernels.cheb_step import cheb_step
from repro.kernels.cheb_sweep import cheb_sweep, jacobi_sweep
from repro.kernels.jacobi_step import jacobi_step

ETA, K = 4, 20            # SGWT J=3 bank at the paper's order
SWEEP_SLOTS = 4           # sensor-graph Block-ELL (8, 128): 4 slots
N_CHIP = 1_000_000        # community graph, one chip, Block-ELL (8, 8)
CHIP_SLOTS = 9
SENSOR_SLOTS = 23         # sensor1m: Block-ELL (8, 8), 23 slots, band 265
SENSOR_BAND = 265


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as one 1-D "graph" mesh."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(topo.devices), ("graph",),
                axis_types=(AxisType.Auto,))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ell(n, slots=SWEEP_SLOTS, block=(8, 128)):
    br, bc = block
    return types.SimpleNamespace(
        blocks=jax.ShapeDtypeStruct((n // br, slots, br, bc), jnp.float32),
        panels=jax.ShapeDtypeStruct((n // br, br, slots * bc), jnp.float32),
        indices=jax.ShapeDtypeStruct((n // br, slots), jnp.int32))


def _largest_admitted(need):
    """Largest 128-multiple n whose modelled footprint the VMEM guard
    admits."""
    budget = ops.DEFAULT_SWEEP_VMEM_BUDGET
    n = 128
    while need(n + 128) <= budget:
        n += 128
    assert need(n) <= budget < need(n + 128)
    return n


@pytest.mark.parametrize("batch", [1, 64])
def test_cheb_sweep_compiles_at_guard_limit(one_chip, batch):
    n = _largest_admitted(lambda m: ops.cheb_sweep_vmem_bytes(
        _ell(m).blocks.shape, m, ETA, batch))
    A = _ell(n)
    _compile(lambda b, i, x, c: cheb_sweep(b, i, x, c, alpha=2.0),
             _spec(one_chip, A.blocks.shape),
             _spec(one_chip, A.indices.shape, jnp.int32),
             _spec(one_chip, (batch, n)), _spec(one_chip, (ETA, K + 1)))


@pytest.mark.parametrize("den", [(0.5, 1.0), (0.5, 1.0, 0.25)])
@pytest.mark.parametrize("batch", [1, 64])
def test_jacobi_sweep_compiles_at_guard_limit(one_chip, batch, den):
    n = _largest_admitted(lambda m: ops.jacobi_sweep_vmem_bytes(
        _ell(m).blocks.shape, m, len(den), batch))
    A = _ell(n)
    _compile(lambda b, i, y, d, w, x0: jacobi_sweep(b, i, y, d, w, x0,
                                                    den=den),
             _spec(one_chip, A.blocks.shape),
             _spec(one_chip, A.indices.shape, jnp.int32),
             _spec(one_chip, (batch, n)), _spec(one_chip, (n,)),
             _spec(one_chip, (30, 2)), _spec(one_chip, (batch, n)))


def test_block_ell_spmv_batched_compiles_at_chip_scale(one_chip):
    A = _ell(N_CHIP, CHIP_SLOTS, (8, 8))
    _compile(lambda p, i, x: block_ell_spmv_batched(p, i, x),
             _spec(one_chip, A.panels.shape),
             _spec(one_chip, A.indices.shape, jnp.int32),
             _spec(one_chip, (64, N_CHIP)))


def test_block_ell_spmv_window_compiles_at_chip_scale(one_chip):
    """The windowed SpMV at sensor1m's shapes, with the group size the
    dispatch guard picks, fits VMEM and SMEM."""
    A = _ell(N_CHIP, SENSOR_SLOTS, (8, 8))
    A.band = SENSOR_BAND
    x = _spec(one_chip, (64, N_CHIP))
    rows = ops.spmv_window_rows(A, x)
    assert rows == 128
    _compile(lambda p, i, x: block_ell_spmv_window(
        p, i, x, band=SENSOR_BAND, rows=rows),
        _spec(one_chip, A.panels.shape),
        _spec(one_chip, A.indices.shape, jnp.int32), x)


def test_cheb_step_compiles_at_chip_scale(one_chip):
    it = _spec(one_chip, (64, N_CHIP))
    _compile(lambda *a: cheb_step(*a, alpha=2.0), it, it, it,
             _spec(one_chip, (ETA, 64, N_CHIP)), _spec(one_chip, (ETA,)))


@pytest.mark.parametrize("case", ["plain", "first_fold", "fold"])
def test_cheb_step_fold_compiles_at_chip_scale(one_chip, case):
    """sensor1m's orders (B = 64, N = 1e6, eta = 7): a plain order, the
    fold that writes the accumulator and the fold that reads it, each
    over the chunk's 7 held iterates, fit VMEM and tile as Mosaic asks,
    and the (eta, B, N) accumulator is updated where it lies."""
    eta = 7
    it = _spec(one_chip, (64, N_CHIP))
    acc = _spec(one_chip, (eta, 64, N_CHIP))
    coef = _spec(one_chip, (eta, eta + 1))

    def order(pt, acc, coef, *held):
        if case == "plain":
            return cheb_step(pt, held[-1], held[-2], alpha=2.0)
        return cheb_step(pt, held[-1], held[-2],
                         acc if case == "fold" else None, coef, alpha=2.0,
                         held=held)

    compiled = jax.jit(order, donate_argnums=1).lower(
        it, acc, coef, *[it] * eta).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_jacobi_step_compiles_at_chip_scale(one_chip):
    it = _spec(one_chip, (64, N_CHIP))
    _compile(lambda q, x, xp, y, d: jacobi_step(q, x, xp, y, d, w=1.0,
                                                 s=0.0),
             it, it, it, it, _spec(one_chip, (N_CHIP,)))


def test_sharded_reorder_compiles_at_chip_scale(four_chips):
    """The move of a four-chip sensor field's (B, eta, N) result from
    partition order back to vertex order, N = 4e6, B = 64, eta = 7, every
    offset's tile as wide as the largest pair of that cell (449,628 rows):
    no all-gather, and the move's own buffers stay under 4 GB a chip
    beside the result it reads and the one it writes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.partition import sharded_reorder

    S, nl, h, offsets = 4, 1_000_000, 449_628, (1, 2, 3)
    spec = P("graph")
    sig = NamedSharding(four_chips, P(None, None, "graph"))

    def back(y, *plan):
        def run(yl, *pl):
            mine = [a[0] for a in pl]
            return sharded_reorder(yl, mine[:3], mine[3], offsets, "graph",
                                   S, back=True)

        return jax.shard_map(run, mesh=four_chips,
                             in_specs=(P(None, None, "graph"),) + (spec,) * 4,
                             out_specs=P(None, None, "graph"),
                             check_vma=False)(y, *plan)

    idx = [_spec(NamedSharding(four_chips, spec), (S, h), jnp.int32)] * 3
    place = _spec(NamedSharding(four_chips, spec), (S, nl), jnp.int32)
    compiled = jax.jit(back).lower(_spec(sig, (64, 7, S * nl)), *idx,
                                   place).compile()
    text = compiled.as_text()
    assert "all-gather" not in text
    assert "collective-permute" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4e9, mem


def test_sharded_solve_takes_its_structure_as_arguments(four_chips,
                                                         monkeypatch):
    """A four-chip general plan's compiled Jacobi solve, shapes only: its
    structure is laid out as shapes on the described chips, so the solve
    lowers only if it takes the structure as arguments; nothing is
    gathered whole and each chip's arguments hold its shard of it."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import graph
    from repro.core.wavelets import sgwt_multipliers
    from repro.dist import GraphOperator
    from repro.dist import partition as pm

    g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(0), n=64,
                                        theta=0.3, kappa=0.35)
    L = np.asarray(g.laplacian(), np.float32)
    lmax = float(g.lambda_max_bound())
    op = GraphOperator(P=L, multipliers=sgwt_multipliers(lmax, 2),
                       lmax=lmax, K=6)
    parts = pm.partition_general(L, 4, block=(8, 8))
    monkeypatch.setattr(jax, "device_put",
                        lambda a, s: _spec(s, np.shape(a), a.dtype))
    plan = op.plan("pallas_halo", mesh=four_chips, partition=parts)
    monkeypatch.undo()
    shard_bytes = sum(np.prod(s.shape) * s.dtype.itemsize
                      for s in plan.structure) // 4
    y = _spec(NamedSharding(four_chips, P(None, "graph")), (3, 64))
    compiled = plan.compiled_solve("jacobi", tau=0.5).lower(y).compile()
    assert "all-gather" not in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= shard_bytes
