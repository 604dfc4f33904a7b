"""Batched ECDSA on the TPU limb engine (``ops.secp_batch``) vs the
host scalar implementation — the ingest validation layer at scale
(SURVEY.md §7.2 step 5; reference hot spots ``ecdsa/native.rs:298-331``
recover and ``:382-395`` verify).

One module-scoped fixture drives everything, and every ladder call runs
at its lane count (``BATCH``), so each ladder compiles once."""

import random

import numpy as np
import pytest

from protocol_tpu.crypto.secp256k1 import (
    EcdsaKeypair,
    EcdsaVerifier,
    recover_public_key,
)
from protocol_tpu.ops import secp_batch as sb

rng = random.Random(0x5EC9)
BATCH = 6


def _chunks(rows, pad):
    """``rows`` in BATCH-lane chunks, each returned as columns with its
    count of real rows; the last is filled up with ``pad`` rows, the way
    client/ingest.py pads a batch."""
    for lo in range(0, len(rows), BATCH):
        chunk = rows[lo:lo + BATCH]
        cols = zip(*(chunk + [pad] * (BATCH - len(chunk))))
        yield [list(c) for c in cols], len(chunk)


@pytest.fixture(scope="module")
def signed():
    kps = [EcdsaKeypair(20_000 + i) for i in range(BATCH)]
    msgs = [rng.randrange(1, sb.SECP_N) for _ in range(BATCH)]
    sigs = [kp.sign(m) for kp, m in zip(kps, msgs)]
    pubs = [(kp.public_key.point.x, kp.public_key.point.y) for kp in kps]
    return kps, msgs, sigs, pubs


class TestVerifyBatch:
    def test_valid_signatures_accepted(self, signed):
        kps, msgs, sigs, pubs = signed
        ok = sb.verify_batch([s.r for s in sigs], [s.s for s in sigs],
                             msgs, pubs)
        assert ok.all()
        # sanity: the host verifier agrees
        for kp, m, s in zip(kps, msgs, sigs):
            assert EcdsaVerifier(s, m, kp.public_key).verify()

    def test_wrong_message_rejected_per_lane(self, signed):
        _, msgs, sigs, pubs = signed
        bad = list(msgs)
        bad[0] += 1
        ok = sb.verify_batch([s.r for s in sigs], [s.s for s in sigs],
                             bad, pubs)
        assert not ok[0] and ok[1:].all()

    def test_swapped_pubkeys_rejected(self, signed):
        _, msgs, sigs, pubs = signed
        rotated = pubs[1:] + pubs[:1]
        ok = sb.verify_batch([s.r for s in sigs], [s.s for s in sigs],
                             msgs, rotated)
        assert not ok.any()

    def test_degenerate_inputs_rejected(self, signed):
        _, msgs, sigs, pubs = signed
        rs = [sigs[0].r, sigs[1].r, 0] + [s.r for s in sigs[3:]]
        ss = [0, sigs[1].s, sigs[2].s] + [s.s for s in sigs[3:]]
        pps = list(pubs)
        pps[1] = (0, 0)  # default pubkey
        ok = sb.verify_batch(rs, ss, msgs, pps)
        assert not ok[0] and not ok[1] and not ok[2]
        assert ok[3:].all()


class TestRecoverBatch:
    def test_bit_exact_vs_host(self, signed):
        _, msgs, sigs, _ = signed
        xs, ys, valid = sb.recover_batch(
            [s.r for s in sigs], [s.s for s in sigs],
            [s.rec_id for s in sigs], msgs)
        assert valid.all()
        for i, (s, m) in enumerate(zip(sigs, msgs)):
            host = recover_public_key(s, m)
            assert (xs[i], ys[i]) == (host.point.x, host.point.y)

    def test_flipped_parity_recovers_different_key(self, signed):
        kps, msgs, sigs, _ = signed
        xs, ys, valid = sb.recover_batch(
            [s.r for s in sigs], [s.s for s in sigs],
            [1 - s.rec_id for s in sigs], msgs)
        assert valid.all()
        for i, kp in enumerate(kps):
            assert (xs[i], ys[i]) != (kp.public_key.point.x,
                                      kp.public_key.point.y)

    def test_unliftable_r_flagged(self, signed):
        """An r whose x³+7 is a quadratic non-residue must come back
        invalid, not crash."""
        _, msgs, sigs, _ = signed
        rs = [s.r for s in sigs]
        # find a non-liftable x
        x = 5
        while pow(x**3 + 7, (sb.SECP_P - 1) // 2, sb.SECP_P) == 1:
            x += 1
        rs[0] = x
        _, _, valid = sb.recover_batch(
            rs, [s.s for s in sigs], [s.rec_id for s in sigs], msgs)
        assert not valid[0]
        assert valid[1:].all()


class TestRecoverImpliesVerify:
    """The license for ingest to drop its second verification ladder
    (VERDICT r4 → r5 ask #1a): a lane ``recover_batch`` marks valid is
    ALGEBRAICALLY guaranteed to verify — R' = z·s⁻¹·G + r·s⁻¹·Q =
    s⁻¹·(z·G + s·R − z·G) = R, so R'.x ≡ r given the r < n range gate.
    The reference keeps the re-check only as a debug assert
    (``ecdsa/native.rs:322-328``); SURVEY.md §7.3 licenses the drop
    with documentation. This suite pins exact equivalence between the
    binding-check mask and the scalar path's recover-then-verify over
    an adversarial population."""

    @pytest.fixture(scope="class")
    def population(self, signed):
        kps, msgs, sigs, pubs = signed
        rng2 = random.Random(0xD1CE)
        rows = []  # (r, s, rec_id, msg)
        for s, m in zip(sigs[:3], msgs[:3]):  # honest
            rows.append((s.r, s.s, s.rec_id, m))
        # honest signature, high-s twin (verify has no low-s rule)
        s0 = sigs[0]
        rows.append((s0.r, sb.SECP_N - s0.s, 1 - s0.rec_id, msgs[0]))
        rows.append((s0.r, s0.s + 1, s0.rec_id, msgs[0]))  # tampered s
        rows.append((s0.r, s0.s, s0.rec_id, msgs[0] + 1))  # wrong msg
        rows.append((0, s0.s, 0, msgs[0]))  # r = 0
        rows.append((s0.r, 0, 0, msgs[0]))  # s = 0
        rows.append((sb.SECP_N, s0.s, 0, msgs[0]))  # r = n
        rows.append((sb.SECP_N + 5, s0.s, 0, msgs[0]))  # r in (n, p)
        rows.append((s0.r, sb.SECP_N + 7, 0, msgs[0]))  # s > n
        x = 5  # non-liftable r (x³+7 a non-residue)
        while pow(x**3 + 7, (sb.SECP_P - 1) // 2, sb.SECP_P) == 1:
            x += 1
        rows.append((x, s0.s, 0, msgs[0]))
        # crafted identity key: R = k·G, m/s = k makes s·R − m·G = ∞ —
        # the scalar path rejects through is_default, the batch path
        # through its not-∞ flag
        from protocol_tpu.crypto.secp256k1 import SECP256K1_GENERATOR
        kR = SECP256K1_GENERATOR.mul(5)
        rows.append((kR.x, 3, kR.y & 1, 15))
        while len(rows) < 16:  # random garbage
            rows.append((rng2.randrange(1, sb.SECP_P),
                         rng2.randrange(1, sb.SECP_N),
                         rng2.randrange(0, 2),
                         rng2.randrange(1, sb.SECP_N)))
        return rows

    @pytest.fixture(scope="class")
    def recovered(self, population):
        """The population through ``recover_batch``, BATCH lanes at a
        time: (xs, ys, valid) for its rows."""
        xs, ys, valid = [], [], []
        for cols, k in _chunks(population, (1, 1, 0, 1)):
            cx, cy, cvalid = sb.recover_batch(*cols)
            xs += cx[:k]
            ys += cy[:k]
            valid.append(cvalid[:k])
        return xs, ys, np.concatenate(valid)

    def test_mask_equals_scalar_recover_then_verify(self, population,
                                                    recovered):
        """new-path valid == the scalar pipeline (recover, then verify
        with the recovered key), lane for lane."""
        from protocol_tpu.crypto.secp256k1 import Signature

        xs, ys, valid = recovered
        for i, (r, s, c, m) in enumerate(population):
            try:
                pk = recover_public_key(Signature(r, s, c), m)
                scalar_ok = EcdsaVerifier(
                    Signature(r, s, c), m, pk).verify()
            except Exception:
                scalar_ok = False
            assert bool(valid[i]) == scalar_ok, (
                f"lane {i}: batch={bool(valid[i])} scalar={scalar_ok}")
            if valid[i]:
                assert (xs[i], ys[i]) == (pk.point.x, pk.point.y)

    def test_valid_lanes_pass_the_redundant_ladder(self, population,
                                                   recovered):
        """Every valid lane survives the full verification ladder —
        the audit-mode cross-check can never change the mask."""
        xs, ys, valid = recovered
        rows = [(r, s, m, q) for (r, s, _, m), q in
                zip(population, zip(xs, ys))]
        ok = np.concatenate([
            sb.verify_batch(*cols)[:k]
            for cols, k in _chunks(rows, (1, 1, 1, (0, 0)))])
        assert ((valid & ok) == valid).all()


class TestHostParityEdges:
    """Divergences caught in review: the batch path must match the host
    verifier on r >= n and full-byte rec_id inputs."""

    def test_r_geq_n_rejected(self, signed):
        """An r at or above the group order must never verify (the host
        compares against raw r, so x mod n < n <= r can't match). For
        secp256k1 r+n rarely fits 256 bits, so craft r >= n directly."""
        _, msgs, sigs, pubs = signed
        from protocol_tpu.crypto.secp256k1 import EcdsaVerifier, Signature

        rs = [s.r for s in sigs]
        rs[0] = sb.SECP_N + 5
        ok = sb.verify_batch(rs, [s.s for s in sigs], msgs, pubs)
        assert not ok[0] and ok[1:].all()
        host_sig = Signature(r=rs[0], s=sigs[0].s, rec_id=sigs[0].rec_id)
        from protocol_tpu.crypto.secp256k1 import PublicKey, AffinePoint
        host = EcdsaVerifier(host_sig, msgs[0],
                             PublicKey(AffinePoint(*pubs[0]))).verify()
        assert host == bool(ok[0])

    def test_full_byte_rec_id_matches_host(self, signed):
        _, msgs, sigs, _ = signed
        rec_ids = [2 if s.rec_id else s.rec_id for s in sigs]
        xs, ys, valid = sb.recover_batch(
            [s.r for s in sigs], [s.s for s in sigs], rec_ids, msgs)
        assert valid.all()
        from protocol_tpu.crypto.secp256k1 import Signature
        for i, (s, m) in enumerate(zip(sigs, msgs)):
            host = recover_public_key(
                Signature(r=s.r, s=s.s, rec_id=rec_ids[i]), m)
            assert (xs[i], ys[i]) == (host.point.x, host.point.y)


class TestRecoverStream:
    """The pipelined split (submit/midstage/finalize + recover_stream)
    must be bit-identical to per-chunk recover_batch — same kernels,
    same within-chunk order; only the host/device interleaving differs."""

    def test_stream_matches_batch_per_chunk(self, signed):
        _, msgs, sigs, _ = signed
        cols = ([s.r for s in sigs], [s.s for s in sigs],
                [s.rec_id for s in sigs], msgs)
        # two chunks of BATCH lanes, the second the first rotated: a
        # result handed to the wrong chunk cannot match
        half = BATCH // 2
        chunks = [cols, tuple(c[half:] + c[:half] for c in cols)]
        streamed = list(sb.recover_stream(iter(chunks)))
        assert len(streamed) == len(chunks)
        for ch, (xs, ys, valid) in zip(chunks, streamed):
            bx, by, bvalid = sb.recover_batch(*ch)
            assert xs == bx and ys == by
            assert (valid == bvalid).all()

    def test_stream_single_chunk_and_empty(self, signed):
        _, msgs, sigs, _ = signed
        ch = ([s.r for s in sigs], [s.s for s in sigs],
              [s.rec_id for s in sigs], msgs)
        (xs, ys, valid), = list(sb.recover_stream([ch]))
        bx, by, bvalid = sb.recover_batch(*ch)
        assert xs == bx and ys == by and (valid == bvalid).all()
        assert list(sb.recover_stream([])) == []

    def test_invalid_lane_flagged_in_stream(self, signed):
        _, msgs, sigs, _ = signed
        rs = [s.r for s in sigs]
        rs[0] = 0  # out of [1, n) — binding range check
        ch = (rs, [s.s for s in sigs],
              [s.rec_id for s in sigs], msgs)
        (_, _, valid), = list(sb.recover_stream([ch]))
        assert not valid[0] and valid[1:].all()


class TestHashSubmitFinalize:
    def test_split_matches_hash_batch(self):
        from protocol_tpu.models.eigentrust import HASHER_WIDTH
        from protocol_tpu.ops.poseidon_batch import (
            get_poseidon_batch_planes,
        )

        pb = get_poseidon_batch_planes(HASHER_WIDTH)
        rows = [[i + 1, 42, i * 7 + 3, 0] for i in range(8)]
        assert pb.hash_finalize(pb.hash_submit(rows)) == pb.hash_batch(rows)
