"""Compile the service's device programs for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each program for a
topology that is only described, which catches what interpret-mode and
CPU runs cannot (unsupported f64 lowerings, layouts, programs that do
not fit the chip's memory).  Covered: the xla allocator tier's
`_phase2_keys_jit` and `_screen_jit` at the (100,80,40) fleet's bucket
shapes, the risk solver's `_candidate_kernel` for both Woodbury shape
classes at the 8192-scenario bucket, and `_pdhg_setup`/`_pdhg_block` at
the forced-PDHG shapes of tests/test_risk.py.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gh, random_instance
from repro.core.stage2 import Stage2System
from repro.core.xla.kernels import _phase2_keys_jit, _screen_jit
from repro.risk.solver import (_SHAPE_CLASSES, _bucket, _candidate_kernel,
                               _pdhg_block, _pdhg_setup)

HBM_BYTES = 16 * 10**9          # one v5e chip
F64, I64, B8 = jnp.float64, jnp.int64, jnp.bool_

# The (100,80,40) fleet (`fleet-scale`, seed 42) as the xla tier pads it:
# 13 multi-start lanes, 64-wide active-cell bucket in Phase 2; screen
# calls of <= 1024 sources over <= 1024 (lane, type) groups with the
# 128-wide active-cell bucket.
I, JK, LANES = 100, 80 * 40, 13
P2_ACT = 64
SCR_GROUPS, SCR_SRCS, SCR_ACT = 1024, 1024, 128


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
    """One described chip, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    """Compile `fn` for `shapes` ([(shape, dtype)]) on the described chip
    and check that its buffers fit the chip's memory."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return compiled


def test_phase2_keys_compiles_at_fleet_scale(one_chip):
    grid = ((I, JK), F64)
    lane = ((LANES,), F64)
    act = ((LANES, P2_ACT), F64)
    _compile(_phase2_keys_jit, [
        grid, grid, grid, grid, ((I, JK), B8), grid, ((JK,), F64),
        ((I,), F64), ((I,), F64), ((), F64), ((LANES,), I64),
        ((LANES, JK), F64), lane, lane, lane, ((LANES, P2_ACT), I64),
        act, act, ((LANES, P2_ACT), B8)], one_chip)


def test_screen_compiles_at_fleet_scale(one_chip):
    grid = ((I, JK), F64)
    src = ((SCR_SRCS,), F64)
    act = ((SCR_GROUPS, SCR_ACT), F64)
    _compile(_screen_jit, [
        grid, ((I, JK), B8), grid, grid, grid, grid, ((JK,), F64),
        ((JK,), F64), ((), F64), ((SCR_GROUPS,), I64), ((SCR_GROUPS,), I64),
        ((SCR_GROUPS, JK), B8), ((SCR_GROUPS, SCR_ACT), I64), act, act,
        ((SCR_GROUPS, SCR_ACT), B8), ((LANES, JK), F64),
        ((SCR_SRCS,), I64), ((SCR_SRCS,), I64), src, src, src, src, src,
        src], one_chip)


def _system(n_types, n_models, n_tiers, seed):
    inst = random_instance(n_types, n_models, n_tiers, seed=seed)
    s = Stage2System(inst, gh(inst))
    return s.nnz_all, s.m_ub + s.I, s.n


@pytest.fixture(scope="module")
def risk_sizes():
    """(E, m, n) of the risk smoke's deployment: GH on (20,20,20)."""
    return _system(20, 20, 20, seed=42)


@pytest.fixture(scope="module")
def pdhg_sizes():
    """(E, m, n) of tests/test_risk.py's forced-PDHG deployment."""
    return _system(10, 8, 8, seed=7)


@pytest.mark.parametrize("q,eg", _SHAPE_CLASSES)
def test_candidate_kernel_compiles(one_chip, risk_sizes, q, eg):
    E, m, n = risk_sizes
    S, K = _bucket(8192), n
    n_fam = len(Stage2System.ROW_FAMILIES)
    _compile(_candidate_kernel, [
        ((S, E), F64), ((S, n), F64), ((2 * S,), jnp.int32), ((m,), F64),
        ((m,), B8), ((E,), I64), ((E,), I64), ((n,), F64), ((E, m), F64),
        ((E, n), F64),
        # the summary's masks and the donated best candidates
        ((n,), B8), ((m, n_fam), B8), ((m,), F64), ((S, 1 + n + m), F64),
        # the anchor pack (`BatchedStage2Solver._pack`)
        ((E,), I64), ((E,), F64), ((E, K), F64), ((K,), F64),
        ((K,), I64), ((K,), F64), ((K,), F64),
        ((eg,), I64), ((eg,), F64), ((eg,), I64), ((eg,), I64),
        ((eg, q), F64), ((eg, K), F64), ((eg, q * q), F64), ((K, q), F64),
        ((K,), I64), ((K,), F64), ((n,), F64), ((K,), I64), ((K,), F64),
        ((K, K), F64), ((K, q), F64)], one_chip)


def test_pdhg_setup_compiles(one_chip, pdhg_sizes):
    E, m, n = pdhg_sizes
    S = _bucket(120)
    _compile(_pdhg_setup, [
        ((S, E), F64), ((S, n), F64), ((m,), F64), ((E,), I64), ((E,), I64),
        ((n,), F64), ((S, 1 + n + m), F64), ((S,), I64)], one_chip)


def test_pdhg_block_compiles(one_chip, pdhg_sizes):
    E, m, n = pdhg_sizes
    S = _bucket(120)
    col, row = ((S, n), F64), ((S, m), F64)
    _compile(_pdhg_block, [
        ((S, E), F64), col, row, col, row, col, ((m,), B8), ((E,), I64),
        ((E,), I64), ((E, m), F64), ((E, n), F64), row, ((S,), F64),
        col, row, col, row, ((), I64)], one_chip)
