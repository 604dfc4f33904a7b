"""Signed attestations for the live traffic, made in set-up.

A copy of the fixture signer (``tools/sign_fixture.py``) kept under the
benchmark, so that the traffic cannot change with the tool. On a TPU
the k·G side (public keys Q = d·G and nonce points R = k·G) and the
attestation hashes run on the device in fixed chunks of ``CHUNK`` lanes
(one compile each, whatever the traffic's size); s = k⁻¹·(z + r·d) mod
n runs on the host with one Montgomery-trick inversion. The first lanes
of each batch are signed again by the program's host signer, and a
difference ends the run. Elsewhere everything runs on the host signer.
The device hashes are the program's own Poseidon kernel, the one its
ingest runs, so the runner compares a sample of them with
``host_hash``, the program's scalar Poseidon on the host.

Nonces are drawn from the run's seed: this makes many valid signatures
fast; it is not key custody.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK = 1 << 15  # lanes per device dispatch, fixed: one shape per program
CHECKED = 8      # lanes of each batch signed again on the host


class SignerMismatch(Exception):
    pass


def _on_device() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def _padded_chunks(items: list, pad) -> list:
    out = []
    for lo in range(0, len(items), CHUNK):
        part = items[lo:lo + CHUNK]
        out.append(part + [pad] * (CHUNK - len(part)))
    return out


@functools.cache
def _base_mul_jit():
    """u·G → affine plain (x, y) limb rows, jitted once."""
    import jax

    from protocol_tpu.ops.fieldops import from_mont
    from protocol_tpu.ops.secp_batch import CTX_P, _fb_sum, _to_affine

    @jax.jit
    def base_mul(u_plain):
        ax, ay = _to_affine(CTX_P, _fb_sum(u_plain))
        return from_mont(CTX_P, ax), from_mont(CTX_P, ay)

    return base_mul


def base_points(scalars) -> list:
    """k·G for every scalar in [1, n): affine ``(x, y)`` ints."""
    scalars = [int(k) for k in scalars]
    if not _on_device():
        from protocol_tpu.crypto.secp256k1 import SECP256K1_GENERATOR

        return [(p.x, p.y) for p in
                (SECP256K1_GENERATOR.mul(k) for k in scalars)]
    import jax.numpy as jnp

    from protocol_tpu.ops.fieldops import from_limbs, to_limbs

    out = []
    base_mul = _base_mul_jit()
    for part in _padded_chunks(scalars, 1):
        ax, ay = base_mul(jnp.asarray(to_limbs(part)))
        out.extend(zip(from_limbs(np.asarray(ax)),
                       from_limbs(np.asarray(ay))))
    return [(int(x), int(y)) for x, y in out[:len(scalars)]]


def addresses_of(points) -> list:
    """20-byte addresses of public key points."""
    from protocol_tpu.crypto.secp256k1 import AffinePoint, PublicKey

    return [PublicKey(AffinePoint(x, y)).to_address_bytes()
            for x, y in points]


def attestations(abouts, values, domain: bytes) -> list:
    """``AttestationData`` for each (about, value)."""
    from protocol_tpu.client.attestation import AttestationData

    return [AttestationData(about=a, domain=domain, value=int(v))
            for a, v in zip(abouts, values)]


def host_hash(att) -> int:
    """Poseidon hash of one ``AttestationData`` by the host's scalar
    path (about 2 ms each)."""
    return int(att.to_scalar().hash())


def attestation_hashes(atts) -> list:
    """Poseidon hash of each ``AttestationData`` (the message signed)."""
    if not _on_device():
        return [host_hash(att) for att in atts]
    scalars = [att.to_scalar() for att in atts]
    from protocol_tpu.models.eigentrust import HASHER_WIDTH
    from protocol_tpu.ops.poseidon_batch import get_poseidon_batch_planes

    rows = [[int(sc.about), int(sc.domain), int(sc.value), int(sc.message)]
            for sc in scalars]
    pb = get_poseidon_batch_planes(HASHER_WIDTH)
    out = []
    for part in _padded_chunks(rows, [0, 0, 0, 0]):
        out.extend(int(h) for h in pb.hash_batch(part))
    return out[:len(rows)]


def sign(privs, msgs, nonces) -> list:
    """``[(r, s, rec_id)]`` per lane, low-s normalized with the parity
    flip, as the program's ``EcdsaKeypair.sign(msg, k)`` makes them."""
    from protocol_tpu.crypto.secp256k1 import N, EcdsaKeypair

    if not _on_device():
        keys, out = {}, []
        for d, z, k in zip(privs, msgs, nonces):
            key = keys.get(d) or keys.setdefault(d, EcdsaKeypair(int(d)))
            sig = key.sign(int(z), int(k))
            out.append((sig.r, sig.s, sig.rec_id))
        return out
    points = base_points(nonces)
    pre = [1]
    for k in nonces:
        pre.append(pre[-1] * int(k) % N)
    inv = pow(pre[-1], -1, N)
    k_inv = [0] * len(nonces)
    for i in range(len(nonces) - 1, -1, -1):
        k_inv[i] = inv * pre[i] % N
        inv = inv * int(nonces[i]) % N
    border = (N - 1) // 2
    out = []
    for (x, y), ki, d, z in zip(points, k_inv, privs, msgs):
        r = x % N
        s = ki * (int(z) % N + r * int(d)) % N
        rec = y & 1
        if s >= border:
            s, rec = N - s, rec ^ 1
        out.append((r, s, rec))
    for i in range(min(CHECKED, len(out))):
        ref = EcdsaKeypair(int(privs[i])).sign(int(msgs[i]), int(nonces[i]))
        if (ref.r, ref.s, ref.rec_id) != out[i]:
            raise SignerMismatch(f"device signature {i} differs from the "
                                 "host signer")
    return out


def check_keys(privs, points) -> None:
    """The first public keys against the host's d·G."""
    from protocol_tpu.crypto.secp256k1 import SECP256K1_GENERATOR

    for d, (x, y) in list(zip(privs, points))[:CHECKED]:
        p = SECP256K1_GENERATOR.mul(int(d))
        if (p.x, p.y) != (x, y):
            raise SignerMismatch("device public key differs from d·G")


def signed_attestations(atts, hashes, privs, nonces) -> list:
    """``SignedAttestationData`` for each attestation, over its hash,
    signed by the matching private key with the matching nonce."""
    from protocol_tpu.client.attestation import (
        SignatureData,
        SignedAttestationData,
    )

    sigs = sign(privs, hashes, nonces)
    return [SignedAttestationData(att, SignatureData(
        r.to_bytes(32, "big"), s.to_bytes(32, "big"), rec))
        for att, (r, s, rec) in zip(atts, sigs)]
