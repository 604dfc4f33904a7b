"""Batched ingest (``client.ingest``) vs the scalar per-attestation
path — identical hashes, recovered keys, and addresses."""

import numpy as np
import pytest

from protocol_tpu.client.attestation import SignedAttestationData
from protocol_tpu.client.ingest import (
    attestation_hashes_batch,
    recover_signers_batch,
)
from protocol_tpu.crypto.secp256k1 import EcdsaKeypair

DOMAIN = b"\x42" + b"\x00" * 19


from conftest import make_signed_attestation


def make_signed(kp: EcdsaKeypair, about: bytes, value: int,
                message: bytes = b"\x00" * 32) -> SignedAttestationData:
    return make_signed_attestation(kp, about, DOMAIN, value, message)


@pytest.fixture(scope="module")
def batch():
    kps = [EcdsaKeypair(31_000 + i) for i in range(5)]
    signed = [
        make_signed(kp, bytes([i + 1]) * 20, 10 * i + 1,
                    message=bytes([i]) * 32)
        for i, kp in enumerate(kps)
    ]
    return kps, signed


class TestBatchedIngest:
    def test_hashes_match_scalar_path(self, batch):
        _, signed = batch
        digs = attestation_hashes_batch(signed)
        for s, d in zip(signed, digs):
            assert d == int(s.attestation.to_scalar().hash())

    def test_recovery_matches_scalar_path(self, batch):
        kps, signed = batch
        pub_keys, addresses, valid = recover_signers_batch(signed)
        assert valid.all()
        for kp, s, pk, addr in zip(kps, signed, pub_keys, addresses):
            scalar_pk = s.recover_public_key()
            assert pk.point.x == scalar_pk.point.x
            assert pk.point.y == scalar_pk.point.y
            assert addr == kp.public_key.to_address_bytes()

    def test_forged_signature_flagged_not_fatal(self, batch):
        kps, signed = batch
        forged = list(signed)
        # signature from key 0 pasted onto a different attestation
        forged[2] = SignedAttestationData(forged[2].attestation,
                                          signed[0].signature)
        pub_keys, addresses, valid = recover_signers_batch(forged)
        # a pasted signature recovers to SOME key, just not the claimed
        # signer's (the opinion layer nulls it by address mismatch); the
        # batch must not crash and the other lanes stay valid
        others = [i for i in range(len(forged)) if i != 2]
        assert all(valid[i] for i in others)
        if valid[2]:
            assert addresses[2] != kps[2].public_key.to_address_bytes()

    def test_empty_batch(self):
        pub_keys, addresses, valid = recover_signers_batch([])
        assert pub_keys == [] and addresses == [] and valid.shape == (0,)

    def test_full_verify_never_changes_the_mask(self, batch):
        """The audit-mode redundant verification ladder must agree with
        the binding checks on every lane — honest AND forged (the
        recover⇒verify property the default path rests on)."""
        kps, signed = batch
        forged = list(signed)
        forged[1] = SignedAttestationData(forged[1].attestation,
                                          signed[3].signature)
        for pop in (signed, forged):
            _, _, v1 = recover_signers_batch(pop, full_verify=True)
            _, _, v2 = recover_signers_batch(pop)
            assert (v1 == v2).all()
        _, _, v_honest = recover_signers_batch(signed)
        assert v_honest.all()


class TestClientBatchedIngest:
    def test_et_setup_identical_between_paths(self):
        """Client(batched_ingest=True) must produce the same ETSetup as
        the scalar path for the same attestations."""
        from protocol_tpu.client.client import Client, ClientConfig

        mnemonic = ("test test test test test test test test test test "
                    "test junk")
        cfg = ClientConfig(domain="0x" + "00" * 20)
        scalar = Client(cfg, mnemonic)
        batched = Client(cfg, mnemonic, chain=scalar.chain,
                         batched_ingest=True)

        from protocol_tpu.client.eth import ecdsa_keypairs_from_mnemonic

        kps = ecdsa_keypairs_from_mnemonic(mnemonic, 3)
        addrs = [kp.public_key.to_address_bytes() for kp in kps]
        clients = [
            Client(cfg, mnemonic, chain=scalar.chain)
            for _ in range(3)
        ]
        for i, c in enumerate(clients):
            c.keypairs = [kps[i]]
            c.attest(addrs[(i + 1) % 3], 5 + i)
            c.attest(addrs[(i + 2) % 3], 9 - i)

        atts = scalar.get_attestations()
        s1 = scalar.et_circuit_setup(atts)
        s2 = batched.et_circuit_setup(atts)
        assert s1.address_set == s2.address_set
        assert s1.pub_inputs.to_bytes() == s2.pub_inputs.to_bytes()
        assert s1.rational_scores == s2.rational_scores
        for a, b in zip(s1.pub_keys, s2.pub_keys):
            assert (a is None) == (b is None)
            if a is not None:
                assert (a.point.x, a.point.y) == (b.point.x, b.point.y)


class TestChunkedPipelineIngest:
    """Above the lane cap (PTPU_INGEST_CHUNK) the product path chunks
    and software-pipelines; results must be identical to the
    single-batch path, including validity masks and full-verify.

    The reference is the single-batch path run on each chunk's lanes
    (lanes are independent), so that 21–22 lanes under a cap of 8 run
    every ladder at bucket 8, the one the rest of the file compiles:
    three chunks put the pipeline's steady state (a chunk submitted
    while the one before it is finalized) between its prologue and
    epilogue, and the short last chunk of 5–6 lanes still pads to 8."""

    CAP = 8

    def _single_batch_per_chunk(self, atts):
        pks, addrs, valid = [], [], []
        for lo in range(0, len(atts), self.CAP):
            p, a, v = recover_signers_batch(atts[lo:lo + self.CAP])
            pks += p
            addrs += a
            valid.append(v)
        return pks, addrs, np.concatenate(valid)

    def test_chunked_matches_single_batch(self, batch, monkeypatch):
        _, signed = batch
        many = (signed * 5)[:22]  # cap 8 → [0-7][8-15][16-21]
        ref_pks, ref_addrs, ref_valid = self._single_batch_per_chunk(many)
        monkeypatch.setenv("PTPU_INGEST_CHUNK", str(self.CAP))
        pks, addrs, valid = recover_signers_batch(many)
        assert (valid == ref_valid).all()
        assert addrs == ref_addrs
        for a, b in zip(pks, ref_pks):
            assert (a is None) == (b is None)
            if a is not None:
                assert a.point == b.point

    def test_chunked_flags_forged_lane(self, batch, monkeypatch):
        kps, signed = batch
        many = (signed * 5)[:21]
        # signature from key 0 pasted onto a different attestation.
        # Lane 19 (fourth copy of lane 4) lands in the SHORT trailing
        # chunk (21 lanes, cap 8 → [0-7][8-15][16-20]) — the
        # padded-chunk boundary case
        many[19] = SignedAttestationData(many[19].attestation,
                                         signed[0].signature)
        ref_pks, ref_addrs, ref_valid = self._single_batch_per_chunk(many)
        monkeypatch.setenv("PTPU_INGEST_CHUNK", str(self.CAP))
        pks, addrs, valid = recover_signers_batch(many)
        assert (valid == ref_valid).all()
        assert addrs == ref_addrs
        if valid[19]:
            assert addrs[19] != kps[4].public_key.to_address_bytes()

    def test_chunked_full_verify_mask_stable(self, batch, monkeypatch):
        _, signed = batch
        many = (signed * 5)[:21]  # cap 8 → [0-7][8-15][16-20]
        monkeypatch.setenv("PTPU_INGEST_CHUNK", str(self.CAP))
        _, _, base = recover_signers_batch(many)
        _, _, audited = recover_signers_batch(many, full_verify=True)
        assert (base == audited).all()
