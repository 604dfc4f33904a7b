"""DeviceProver cache alternation (zk/prover_fast.py's MRU cache of
DeviceProvers) across two domain sizes. A file of its own: its k=6 and
k=7 prover compiles run beside test_prover_tpu.py's, whose proves all
share one k."""

import pytest

pytest.importorskip("jax")

from protocol_tpu import native  # noqa: E402

if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)


def test_device_prover_cache_alternation(monkeypatch):
    """The Threshold cycle's access pattern: two pks alternating proves
    in one process. The MRU cache must keep BOTH DeviceProvers alive
    (identity-stable across the alternation — no re-init), suspend the
    inactive one, and resume must rebuild bit-identical state: every
    proof stays byte-equal to the host prover. Also covers deep
    suspend (static tables dropped and rebuilt)."""
    import random

    # pin the knobs the asserts depend on (a measurement environment
    # may export the single-slot fallback)
    monkeypatch.setenv("PTPU_DP_CACHE", "2")
    monkeypatch.delenv("PTPU_DP_SUSPEND", raising=False)

    from protocol_tpu.utils.fields import BN254_FR_MODULUS as R
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk.plonk import ConstraintSystem, verify

    def mk(seed, rows, k):
        rng = random.Random(seed)
        cs = ConstraintSystem(lookup_bits=6)
        for _ in range(rows):
            a, b = rng.randrange(50), rng.randrange(50)
            cs.add_row([a, b, (a * b + a) % R], q_a=1, q_mul_ab=1,
                       q_c=R - 1)
        cs.public_input(seed)
        cs.check_satisfied()
        params = pf.setup_params_fast(k, seed=b"dpcache%d" % seed)
        return params, pf.keygen_fast(params, cs, k=k, eval_pk=True), cs

    pa = mk(7, 20, 6)
    pb = mk(8, 40, 7)

    pf._DEVICE_PROVERS.clear()
    try:
        seen = {}
        for rnd, (params, pk, cs) in enumerate((pa, pb, pa, pb, pa)):
            r1, r2 = random.Random(90 + rnd), random.Random(90 + rnd)
            proof_dev = pf.prove_fast_tpu(params, pk, cs,
                                          randint=lambda: r1.randrange(R))
            proof_host = pf.prove_fast(params, pk, cs,
                                       randint=lambda: r2.randrange(R))
            assert proof_dev == proof_host, f"round {rnd} diverged"
            assert verify(params, pk, cs.public_values(), proof_dev)
            dp_now = pf._DEVICE_PROVERS[0][1]
            key = id(pk)
            if key in seen:
                assert seen[key] is dp_now, "DeviceProver was rebuilt"
            seen[key] = dp_now
        assert len(pf._DEVICE_PROVERS) == 2
        # the inactive prover must be suspended (no resident ext tables)
        inactive = pf._DEVICE_PROVERS[1][1]
        assert inactive.fixed_ext == [] and inactive.sigma_ext == []

        # deep suspend drops the static tables too; resume + prove must
        # still match the host byte-for-byte
        params, pk, cs = pa
        dp_a = next(d for p0, d in pf._DEVICE_PROVERS if p0 is pk)
        dp_a.suspend(deep=True)
        assert not dp_a._tables_live
        r1, r2 = random.Random(1234), random.Random(1234)
        proof_dev = pf.prove_fast_tpu(params, pk, cs,
                                      randint=lambda: r1.randrange(R))
        proof_host = pf.prove_fast(params, pk, cs,
                                   randint=lambda: r2.randrange(R))
        assert proof_dev == proof_host
        assert dp_a._tables_live
    finally:
        pf._DEVICE_PROVERS.clear()
