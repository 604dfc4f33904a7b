"""Proving-facade tests (``protocol_tpu.zk.api``): the byte-artifact
surface the CLI persists via EigenFile, twin of the reference Client's
proving wrappers (eigentrust/src/lib.rs:239-336, 537-604).

The full ET prove/verify cycle is ``slow``-marked like every real-prover
test (the reference #[ignore]s its equivalents, dynamic_sets/mod.rs:870).
"""

from fractions import Fraction

import pytest

from protocol_tpu.client.circuit_io import ETPublicInputs, ThPublicInputs, ThSetup
from protocol_tpu.crypto.secp256k1 import EcdsaKeypair
from protocol_tpu.models.eigentrust import (
    Attestation,
    EigenTrustSet,
    SignedAttestation,
)
from protocol_tpu.utils.errors import EigenError
from protocol_tpu.utils.fields import Fr
from protocol_tpu.zk import api
from protocol_tpu.zk.api import CircuitShape

DOMAIN = Fr(42)

# smallest real shape: 2 peers, 2 iterations (ECDSA chips dominate rows,
# so fewer iterations only trims the tail), small range table — the
# canonical instance lives in the api module (CLI --shape tiny and the
# measurement tools share it)
from protocol_tpu.zk.api import TINY_SHAPE as TINY  # noqa: E402


def tiny_et_setup(shape=TINY):
    """A real ETSetup built directly (no chain): sparse opinions so the
    witness differs structurally-in-values from api's dummy fixture."""
    from protocol_tpu.client.circuit_io import ETSetup
    from protocol_tpu.crypto.poseidon import PoseidonSponge
    from protocol_tpu.models.eigentrust import HASHER_WIDTH

    n = shape.num_neighbours
    kps = [EcdsaKeypair(5000 + i) for i in range(n)]
    addrs = [kp.public_key.to_address() for kp in kps]
    native = EigenTrustSet(n, shape.num_iterations, shape.initial_score,
                           DOMAIN)
    for a in addrs:
        native.add_member(a)
    matrix = [[None] * n for _ in range(n)]
    op_hashes = []
    rows = {0: [None, 400], 1: [600, None]}
    for i, row in rows.items():
        signed = []
        for j in range(n):
            if row[j]:
                att = Attestation(about=addrs[j], domain=DOMAIN,
                                  value=Fr(row[j]), message=Fr.zero())
                sa = SignedAttestation(att, kps[i].sign(int(att.hash())))
                signed.append(sa)
                matrix[i][j] = sa
            else:
                signed.append(None)
        op_hashes.append(native.update_op(kps[i].public_key, signed))
    scores = native.converge()
    ratios = native.converge_rational()
    sponge = PoseidonSponge(HASHER_WIDTH)
    sponge.update(op_hashes)
    pub_inputs = ETPublicInputs(list(addrs), scores, DOMAIN, sponge.squeeze())
    return ETSetup(
        address_set=[a.to_bytes_be()[12:] for a in addrs],
        attestation_matrix=matrix,
        pub_keys=[kp.public_key for kp in kps],
        pub_inputs=pub_inputs,
        rational_scores=ratios,
    )


class TestApiFast:
    def test_kzg_params_roundtrip(self):
        from protocol_tpu.zk.kzg import KZGParams

        data = api.generate_kzg_params(6, seed=b"api-test")
        p = KZGParams.from_bytes(data)
        assert p.k == 6 and len(p.g1_powers) >= (1 << 6)
        # deterministic for a fixed seed
        assert api.generate_kzg_params(6, seed=b"api-test") == data

    def test_verify_et_rejects_malformed_public_inputs(self):
        with pytest.raises(EigenError):
            api.verify_et(b"", b"", b"\x00" * 31, b"", shape=TINY)

    def test_th_proof_requires_et_context(self):
        setup = ThSetup(
            ThPublicInputs(Fr(1), Fr(2), True), [], [],
        )
        with pytest.raises(EigenError) as e:
            api.generate_th_proof(b"", b"", setup, shape=TINY)
        assert "EigenTrust context" in str(e.value)

    def test_accumulator_limb_decoding_errors(self):
        with pytest.raises(EigenError):
            api._accumulator_from_limbs([Fr(1)] * 15)
        # 16 limbs that do not land on the curve
        with pytest.raises(EigenError):
            api._accumulator_from_limbs([Fr(1)] * 16)

    def test_accumulator_limb_roundtrip(self):
        from protocol_tpu.zk.aggregator import accumulator_limbs
        from protocol_tpu.zk.bn254 import G1_GEN, g1_mul

        lhs = g1_mul(G1_GEN, 7)
        rhs = g1_mul(G1_GEN, 11)
        limbs = accumulator_limbs((lhs, rhs))
        assert api._accumulator_from_limbs(limbs) == (lhs, rhs)


@pytest.mark.slow
@pytest.mark.watchdog(1500)  # k=20 keygen + prove in setup: 396–480 s
class TestApiProveCycle:
    """Full byte-artifact cycle at the tiny real shape. Key structural
    property under test: the proving key generated over the *dummy*
    witness proves a circuit built from a *different* (sparse) witness —
    i.e. circuit structure is witness-independent, which is what makes
    the reference's dummy-circuit keygen sound (lib.rs:537-558)."""

    @pytest.fixture(scope="class")
    def artifacts(self):
        params = api.generate_kzg_params(20, seed=b"api-cycle")
        pk = api.generate_et_pk(params, shape=TINY)
        setup = tiny_et_setup()
        proof = api.generate_et_proof(params, pk, setup, shape=TINY)
        return params, pk, setup, proof

    def test_et_proof_verifies(self, artifacts):
        params, pk, setup, proof = artifacts
        pub_bytes = setup.pub_inputs.to_bytes()
        assert api.verify_et(params, pk, pub_bytes, proof, shape=TINY)

    def test_et_proof_tamper_rejected(self, artifacts):
        params, pk, setup, proof = artifacts
        bad = bytearray(proof)
        bad[len(bad) // 2] ^= 1
        assert not api.verify_et(params, pk, setup.pub_inputs.to_bytes(),
                                 bytes(bad), shape=TINY)

    def test_et_wrong_publics_rejected(self, artifacts):
        """Any genuinely different public input must fail verification.
        NB the n=2 cycle converges to EQUAL scores, so reversing the
        score list is a no-op — mutate a score value and the participant
        order instead (each is a distinct public-input vector)."""
        params, pk, setup, proof = artifacts
        pubs = ETPublicInputs.from_bytes(setup.pub_inputs.to_bytes(),
                                         TINY.num_neighbours)
        assert int(pubs.scores[0]) == int(pubs.scores[1])  # the trap
        pubs.scores = [pubs.scores[0] + Fr(1), pubs.scores[1]]
        assert not api.verify_et(params, pk, pubs.to_bytes(), proof,
                                 shape=TINY)
        pubs2 = ETPublicInputs.from_bytes(setup.pub_inputs.to_bytes(),
                                          TINY.num_neighbours)
        pubs2.participants = list(reversed(pubs2.participants))
        assert not api.verify_et(params, pk, pubs2.to_bytes(), proof,
                                 shape=TINY)

    def test_proof_pubs_divergence_rejected(self, artifacts):
        params, pk, setup, _ = artifacts
        original = setup.pub_inputs.scores
        setup.pub_inputs.scores = [original[0] + Fr(1), *original[1:]]
        try:
            with pytest.raises(EigenError):
                api.generate_et_proof(params, pk, setup, shape=TINY)
        finally:
            setup.pub_inputs.scores = original


@pytest.mark.slow
class TestApiThresholdCycle:
    """Full Threshold byte-artifact cycle at the tiny shape: th pk over
    a dummy aggregated circuit (which proves a dummy inner ET snark),
    real th proof over a different witness, verify incl. the deferred
    KZG decider on the accumulator limbs. The reference #[ignore]s the
    same flow (threshold/mod.rs:850,951)."""

    @pytest.mark.watchdog(7200)  # 2,303 s on an 8-core CPU host
    def test_th_cycle(self):
        # k=21 — the reference's own Threshold KZG degree
        # (circuits/mod.rs:59): the batched-MSM verifier fold brought
        # the aggregated circuit back under 2^21 (r3; measured end to
        # end by tools/th_cycle.py --k 21: 2732 s on the device path)
        params = api.generate_kzg_params(21, seed=b"api-th-cycle")
        th_pk = api.generate_th_pk(params, shape=TINY)

        setup_et = tiny_et_setup()
        # build the ThSetup by hand from the ET context (no chain)
        from protocol_tpu.client.circuit_io import ThPublicInputs, ThSetup
        from protocol_tpu.models.threshold import Threshold

        index = 1
        threshold = 500
        ratio = setup_et.rational_scores[index]
        th = Threshold(setup_et.pub_inputs.scores[index], ratio,
                       Fr(threshold), num_limbs=TINY.num_limbs,
                       power_of_ten=TINY.power_of_ten,
                       num_neighbours=TINY.num_neighbours,
                       initial_score=TINY.initial_score)
        setup = ThSetup(
            ThPublicInputs(
                address=setup_et.pub_inputs.participants[index],
                threshold=Fr(threshold),
                threshold_check=th.check_threshold(),
            ),
            th.num_decomposed, th.den_decomposed,
            et_setup=setup_et, ratio=ratio,
        )
        proof = api.generate_th_proof(params, th_pk, setup, shape=TINY)
        assert len(setup.pub_inputs.agg_instances) == 16
        pub_bytes = setup.pub_inputs.to_bytes()
        assert api.verify_th(params, th_pk, pub_bytes, proof, shape=TINY)
        bad = bytearray(proof)
        bad[len(bad) // 2] ^= 1
        assert not api.verify_th(params, th_pk, pub_bytes, bytes(bad),
                                 shape=TINY)
