"""Multi-chip sharded NTT (parallel/ntt.py) vs the single-device kernel
— bit-exactness over the virtual 8-device mesh, the proving stack's
distributed seam."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from protocol_tpu.ops import fieldops2 as f2  # noqa: E402
from protocol_tpu.ops import ntt_tpu  # noqa: E402
from protocol_tpu.parallel.mesh import make_mesh  # noqa: E402
from protocol_tpu.parallel.ntt import ntt_sharded  # noqa: E402
from protocol_tpu.utils.fields import BN254_FR_MODULUS as P  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the virtual 8-device mesh"
)

# one compiled program per (plan, mesh): called outside jit, shard_map
# runs its body primitive by primitive over the mesh, which takes some
# 110 s a call on an 8-device XLA:CPU mesh against ~3 s compiled
_sharded = jax.jit(ntt_sharded, static_argnums=(1, 2))


def _rand_planes(n, seed):
    rng = np.random.default_rng(seed)
    vals = [int.from_bytes(rng.bytes(32), "little") % P for _ in range(n)]
    mont = [v * f2.R_MONT % P for v in vals]
    return jnp.asarray(f2.ints_to_planes(mont))


@pytest.fixture(scope="module")
def single_device():
    """k -> (input, single-device NTT of it), each computed once."""
    cache = {}

    def get(k):
        if k not in cache:
            x = _rand_planes(1 << k, 100 + k)
            cache[k] = x, np.asarray(ntt_tpu.ntt(x, ntt_tpu.NttPlan.get(k)))
        return cache[k]

    return get


@pytest.mark.parametrize("k,shards", [(10, 8), (10, 2), (8, 4)])
def test_sharded_ntt_bit_exact(single_device, k, shards):
    x, expect = single_device(k)
    got = np.asarray(_sharded(x, ntt_tpu.NttPlan.get(k),
                              make_mesh(shards)))
    assert np.array_equal(got, expect)


def test_sharded_ntt_rejects_bad_shard_count():
    plan = ntt_tpu.NttPlan.get(8)  # B = 16
    mesh = make_mesh(8)
    x = _rand_planes(1 << 8, 1)
    # fine: 16 % 8 == 0; then check a non-dividing count via a fake
    got = _sharded(x, plan, mesh)
    assert got.shape == (f2.L, 1 << 8)
    plan6 = ntt_tpu.NttPlan.get(6)  # B = 8, A = 8
    mesh3 = make_mesh(3)
    with pytest.raises(ValueError):
        _sharded(_rand_planes(1 << 6, 2), plan6, mesh3)
