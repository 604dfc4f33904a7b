"""Lane-sharded pubkey recovery (``parallel/ingest.py``) over the
virtual 8-device mesh, and the multichip dry-run's ingest stage.

On XLA:CPU the full sharded GLV ladder (``shard_glv=True``) is a
program of its own, about three minutes to compile, so these tests have
a file to themselves and run beside the rest of the suite. The ladder's
reference is the host's exact scalar recovery, which the single-device
ladder matches bit for bit (``test_secp_batch.py``); a single-device
run here would add a second ladder compile of the same length. The
sharded prep stage against the single-device one: the dry-run check."""

import random

import pytest

jax = pytest.importorskip("jax")

from protocol_tpu.crypto.secp256k1 import (  # noqa: E402
    EcdsaKeypair,
    recover_public_key,
)
from protocol_tpu.ops.secp_batch import SECP_N  # noqa: E402
from protocol_tpu.parallel.ingest import sharded_recover_batch  # noqa: E402


class TestShardedIngest:
    def test_lane_sharded_recovery_bit_identical(self):
        """Every valid lane recovers the signer's key exactly; the
        binding-check reject survives the sharding."""
        import numpy as np
        from jax.sharding import Mesh

        if jax.device_count() < 8:
            pytest.skip("needs the 8-device virtual mesh (conftest)")
        mesh = Mesh(np.array(jax.devices()[:8]), ("lanes",))
        rng = random.Random(0xB00)
        k = 16
        kps = [EcdsaKeypair(61_000 + i) for i in range(k)]
        msgs = [rng.randrange(1, SECP_N) for _ in range(k)]
        sigs = [kp.sign(m) for kp, m in zip(kps, msgs)]
        rs = [s.r for s in sigs]
        ss = [s.s for s in sigs]
        recs = [s.rec_id for s in sigs]
        ss[3] = 0  # binding-check reject must survive the sharding
        # shard_glv=True forces the FULL sharded ladder even on the CPU
        # mesh (the default trims it there for compile budget)
        xs, ys, valid = sharded_recover_batch(rs, ss, recs, msgs, mesh,
                                              shard_glv=True)
        assert not valid[3] and valid.sum() == k - 1
        for i, (sig, m) in enumerate(zip(sigs, msgs)):
            if i != 3:
                host = recover_public_key(sig, m)
                assert (xs[i], ys[i]) == (host.point.x, host.point.y)
                assert host == kps[i].public_key

    def test_dryrun_ingest_stage_within_cpu_budget(self):
        """Timing guard for the multichip dry-run's ingest stage: the
        r5 regression was minutes-long GLV-ladder XLA:CPU compiles
        timing out the whole dryrun (MULTICHIP_r05.json rc=124). The
        stage's CPU form (prep-stage parity, no ladder) must stay
        inside a small fraction of the dry-run's budget — if this starts
        failing, a minutes-long compile crept back into the dryrun."""
        import time

        import jax

        if jax.device_count() < 8:
            pytest.skip("needs the 8-device virtual mesh (conftest)")
        import __graft_entry__ as graft
        from protocol_tpu.parallel import make_mesh

        mesh = make_mesh(8)
        t0 = time.monotonic()
        graft._dryrun_sharded_ingest(8, mesh)
        wall = time.monotonic() - t0
        budget = float(
            __import__("os").environ.get("PTPU_DRYRUN_INGEST_BUDGET_S",
                                         "600"))
        assert wall < budget, (
            f"dryrun ingest stage took {wall:.0f}s (> {budget:.0f}s): "
            "a minutes-long XLA:CPU compile is back on the dryrun path")

    def test_indivisible_lane_count_rejected(self):
        import jax
        from jax.sharding import Mesh
        import numpy as np

        from protocol_tpu.parallel.ingest import sharded_recover_batch

        ndev = min(8, jax.device_count())
        if ndev < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = Mesh(np.array(jax.devices()[:ndev]), ("lanes",))
        with pytest.raises(ValueError):
            sharded_recover_batch([1] * (ndev + 1), [1] * (ndev + 1),
                                  [0] * (ndev + 1), [1] * (ndev + 1),
                                  mesh)
