"""Main-path kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler is installed here, so these compiles refuse what the
chip's compiler would refuse (tile alignment, fast-memory budgets) at no
chip time. Nothing runs: they say nothing about results or times. Every
case asserts the Mosaic kernel made it into the program
(``tpu_custom_call``). The topology is described inside a fixture, never
at import, so pytest-xdist workers collect the same tests and only the
worker given this file loads the TPU library. The persistent compile
cache is off around the compiles: a described chip's entries cannot be
read back. The programs compile with x64 off, as they run in production
(the suite's conftest turns it on for the float64 oracles).
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, x):
    import jax

    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [2_097_152, 16_384],
                         ids=["10M-level0-stage", "small-stage"])
def test_clos_lane_gather_compiles(one_chip, rows):
    """The Clos lane-gather kernel at the 10M graph's 2^28-slot level-0
    stage (2,097,152 x 128) and at a small stage."""
    import jax
    import jax.numpy as jnp

    from protocol_tpu.ops.clos import _lane_perm_pallas

    x = jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((rows, 128), jnp.uint8, sharding=one_chip)
    with jax.enable_x64(False):
        text = jax.jit(_lane_perm_pallas).lower(x, idx).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,m", [(524_288, 32), (32_768, 2), (4_096, 64)],
                         ids=["s21-edge", "s21-state", "widest"])
def test_clos_inner_level_compiles(one_chip, rows, m):
    """The fused inner-level kernel at the Graph500 scale-21 routes'
    innermost levels (2^26 slots in 32-row blocks, 2^22 in 2-row
    blocks) and at the widest block a narrow level has (64 rows)."""
    import jax
    import jax.numpy as jnp

    from protocol_tpu.ops.clos import _inner_level_pallas

    x = jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((rows, 128), jnp.uint8, sharding=one_chip)
    with jax.enable_x64(False):
        text = jax.jit(lambda *a: _inner_level_pallas(*a, m)).lower(
            x, idx, idx, idx).compile().as_text()
    assert "tpu_custom_call" in text


def test_converge_routed_adaptive_compiles(one_chip):
    """The whole routed converge program on a 65,536-peer BA operator,
    with the Pallas route forced (``_use_pallas`` would see the CPU)."""
    import jax
    import jax.numpy as jnp

    from protocol_tpu.graph import barabasi_albert_edges
    from protocol_tpu.ops.routed import (
        build_routed_operator,
        converge_routed_adaptive,
        routed_arrays,
    )

    n = 65_536
    src, dst, val = barabasi_albert_edges(n, 8, seed=0)
    op = build_routed_operator(n, src, dst, val)
    with jax.enable_x64(False):
        arrs, static = routed_arrays(op, dtype=jnp.float32, alpha=0.1,
                                     pallas=True)
        arrs = jax.tree_util.tree_map(lambda a: _on(one_chip, a), arrs)
        s0 = jax.ShapeDtypeStruct((op.n_state,), jnp.float32,
                                  sharding=one_chip)
        compiled = converge_routed_adaptive.lower(
            arrs, static, s0, tol=1e-6, max_iterations=500).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
