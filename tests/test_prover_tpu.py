"""DeviceProver (zk/prover_tpu.py) vs the host C++ prover kernels —
bit-exactness of every round-3/4 building block at a small domain."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from protocol_tpu import native  # noqa: E402
from protocol_tpu.utils.fields import BN254_FR_MODULUS as P  # noqa: E402

if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

# These run on ANY backend: the CPU harness included (the round-2 CPU
# compile hang was a lax.while_loop pathology in fieldops2.pack16,
# fixed by unrolling). `PTPU_TPU=1 pytest tests/test_prover_tpu.py`
# additionally overrides the conftest CPU pin (see conftest.py) to run
# this battery against the real TPU chip — failures there are real
# failures, never skips.

from protocol_tpu.ops import fieldops2 as f2  # noqa: E402
from protocol_tpu.zk import prover_tpu as ptpu  # noqa: E402
from protocol_tpu.zk.domain import EvaluationDomain  # noqa: E402
from protocol_tpu.zk.plonk import _find_coset_shifts  # noqa: E402

K = int(__import__("os").environ.get("PTPU_TEST_K", "6"))
N = 1 << K
EXT_N = N * 4  # 4n extension coset (z-split)
SHIFT = _find_coset_shifts(EXT_N, 2)[1]


def _rand_u64(n, seed):
    rng = np.random.default_rng(seed)
    vals = [int.from_bytes(rng.bytes(32), "little") % P for _ in range(n)]
    out = np.zeros((n, 4), dtype="<u8")
    for i, v in enumerate(vals):
        out[i] = np.frombuffer(int(v).to_bytes(32, "little"), dtype="<u8")
    return out, vals


@pytest.fixture(scope="module")
def dp():
    fixed = [_rand_u64(N, 100 + i)[0] for i in range(9)]
    sigma = [_rand_u64(N, 200 + i)[0] for i in range(6)]
    return ptpu.DeviceProver(K, SHIFT, fixed, sigma), fixed, sigma


def _host_ext(coeffs_u64, blinds=None):
    """Host oracle: blinded coeffs zero-padded to 4n, coset-scaled,
    NTT'd — the exact prove_fast round-3 ``ext()``."""
    fk = native.FieldKernel(P)
    de = EvaluationDomain(K + 2)
    arr = np.zeros((EXT_N, 4), dtype="<u8")
    m = len(coeffs_u64)
    arr[:m] = coeffs_u64
    if blinds:
        for i, b in enumerate(blinds):
            lo = int.from_bytes(arr[i].tobytes(), "little")
            hi = int.from_bytes(arr[N + i].tobytes(), "little")
            arr[i] = np.frombuffer(
                int((lo - b) % P).to_bytes(32, "little"), dtype="<u8")
            arr[N + i] = np.frombuffer(
                int((hi + b) % P).to_bytes(32, "little"), dtype="<u8")
    fk.coset_scale(arr, SHIFT)
    fk.ntt(arr, de.omega)
    return arr


def _chunks_to_host_order(dp_obj, chunks):
    """Device chunk arrays (FS layout per chunk) → host ext order
    (m = j + 4i)."""
    out = np.zeros((EXT_N, 4), dtype="<u8")
    for j, ch in enumerate(chunks):
        nat = ptpu.natural_from_fs(ch, dp_obj.A, dp_obj.B)
        vals = ptpu.download_std(nat)
        out[j::4] = vals
    return out


def test_ext_chunks_match_host(dp):
    dp_obj, _, _ = dp
    coeffs_u64, _ = _rand_u64(N, 7)
    dev_coeffs = ptpu.upload_mont(coeffs_u64)
    chunks = dp_obj.ext_chunks(dev_coeffs)
    got = _chunks_to_host_order(dp_obj, chunks)
    assert np.array_equal(got, _host_ext(coeffs_u64))


def test_ext_chunks_blinded_match_host(dp):
    dp_obj, _, _ = dp
    coeffs_u64, _ = _rand_u64(N, 8)
    blinds = [12345, 999, 31337]
    dev_coeffs = ptpu.upload_mont(coeffs_u64)
    chunks = dp_obj.ext_chunks(dev_coeffs, blinds=blinds)
    got = _chunks_to_host_order(dp_obj, chunks)
    assert np.array_equal(got, _host_ext(coeffs_u64, blinds=blinds))


def test_roll_matches_omega_shift(dp):
    """fs_roll_next must equal evaluating p(ωX) (the host coset_scale-
    by-omega route)."""
    dp_obj, _, _ = dp
    coeffs_u64, _ = _rand_u64(N, 9)
    dev_coeffs = ptpu.upload_mont(coeffs_u64)
    rolled = [ptpu.fs_roll_next(c, dp_obj.A, dp_obj.B)
              for c in dp_obj.ext_chunks(dev_coeffs)]
    got = _chunks_to_host_order(dp_obj, rolled)

    fk = native.FieldKernel(P)
    shifted = coeffs_u64.copy()
    fk.coset_scale(shifted, EvaluationDomain(K).omega)
    assert np.array_equal(got, _host_ext(shifted))


def test_intt_ext_matches_host(dp):
    dp_obj, _, _ = dp
    ext_u64 = _rand_u64(EXT_N, 11)[0]
    # device chunks from the host-order ext array
    chunks = []
    for j in range(4):
        nat = ptpu.upload_mont(np.ascontiguousarray(ext_u64[j::4]))
        chunks.append(ptpu.fs_from_natural(nat, dp_obj.A, dp_obj.B))
    dev_chunks = dp_obj.intt_ext(chunks)
    got = np.concatenate([ptpu.download_std(dev_chunks[u])
                          for u in range(4)])

    fk = native.FieldKernel(P)
    de = EvaluationDomain(K + 2)
    host = ext_u64.copy()
    fk.ntt(host, de.omega, inverse=True)
    fk.coset_scale(host, SHIFT, invert=True)
    assert np.array_equal(got, host)


def test_barycentric_eval(dp):
    dp_obj, _, _ = dp
    evals_u64, vals = _rand_u64(N, 13)
    dev = ptpu.upload_mont(evals_u64)
    zeta = 0x1234567890ABCDEF1234567
    # host: iNTT then Horner
    fk = native.FieldKernel(P)
    coeffs = evals_u64.copy()
    fk.ntt(coeffs, EvaluationDomain(K).omega, inverse=True)
    stacked = coeffs.reshape(1, N, 4)
    expect = fk.poly_eval_many(stacked, zeta)[0]
    assert dp_obj.eval_at(dev, zeta) == int(expect)


@pytest.fixture(scope="module")
def tiny():
    """(params, eval-form pk, cs) of the lockstep circuit (a lookup row
    and a copy among its gates) at k = K: every full prove in this file
    runs at that one domain size, so the device prover's programs
    compile once."""
    import random

    from protocol_tpu.utils.fields import BN254_FR_MODULUS as R
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk.plonk import ConstraintSystem

    rng = random.Random(11)
    cs = ConstraintSystem(lookup_bits=6)
    for _ in range(20):
        a, b = rng.randrange(50), rng.randrange(50)
        cs.add_row([a, b, (a * b + a) % R], q_a=1, q_mul_ab=1, q_c=R - 1)
    lk = cs.lookup_row(37)
    row = cs.add_row([37], q_a=1, q_const=R - 37)
    cs.copy(lk, (0, row))
    cs.public_input(777)
    cs.check_satisfied()
    params = pf.setup_params_fast(K, seed=b"lockstep")
    return params, pf.keygen_fast(params, cs, k=K, eval_pk=True), cs


def test_prove_fast_tpu_bytes_equal_host(tiny):
    """End-to-end transcript lockstep: for the same blinding stream the
    integrated device prover must emit BYTE-IDENTICAL proofs to the host
    prover (prover_fast.py's LOCKSTEP WARNING, enforced). Runs on every
    backend — this is the test that makes an absorb-order divergence
    between the two provers fail CI instead of merging green."""
    import random

    from protocol_tpu.utils.fields import BN254_FR_MODULUS as R
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk.plonk import verify

    params, pk, cs = tiny
    r1, r2 = random.Random(42), random.Random(42)
    proof_tpu = pf.prove_fast_tpu(params, pk, cs,
                                  randint=lambda: r1.randrange(R))
    proof_host = pf.prove_fast(params, pk, cs,
                               randint=lambda: r2.randrange(R))
    assert proof_tpu == proof_host
    assert verify(params, pk, cs.public_values(), proof_tpu)


def test_streaming_quotient_matches_resident(dp):
    """The k≥21 streaming quotient (pk ext chunks generated on the fly)
    must be BIT-identical to the resident-table path — in BOTH its
    fused (one program per chunk, PTPU_FUSED_QUOTIENT default) and
    unfused (dispatch-chain fallback) forms."""
    dp_obj, fixed_u64, sigma_u64 = dp
    dp_stream = ptpu.DeviceProver(K, SHIFT, fixed_u64, sigma_u64,
                                  ext_resident=False)
    rng = np.random.default_rng(33)
    wires = [ptpu.upload_mont(_rand_u64(N, 500 + w)[0]) for w in range(6)]
    z = ptpu.upload_mont(_rand_u64(N, 510)[0])
    m = ptpu.upload_mont(_rand_u64(N, 511)[0])
    phi = ptpu.upload_mont(_rand_u64(N, 512)[0])
    pi = ptpu.upload_mont(_rand_u64(N, 513)[0])
    uv = [ptpu.upload_mont(_rand_u64(N, 520 + i)[0]) for i in range(4)]
    beta, gamma, beta_lk, alpha = [int(x) % P for x in
                                   rng.integers(1, 2**62, 4)]
    shifts = _find_coset_shifts(N, 6)
    ch_r = dp_obj.challenge_planes(beta, gamma, beta_lk, alpha, shifts)
    ch_s = dp_stream.challenge_planes(beta, gamma, beta_lk, alpha, shifts)
    dp_fixed = ptpu.DeviceProver(K, SHIFT, fixed_u64, sigma_u64,
                                 ext_resident="fixed")
    assert dp_fixed.fixed_ext and not dp_fixed.ext_resident \
        and not dp_fixed.sigma_ext
    ch_f = dp_fixed.challenge_planes(beta, gamma, beta_lk, alpha, shifts)
    for j in (0, 3):
        we_r = [dp_obj.ext_chunk(dp_obj.intt_natural(w), j) for w in wires]
        ze_r = dp_obj.ext_chunk(dp_obj.intt_natural(z), j)
        me_r = dp_obj.ext_chunk(dp_obj.intt_natural(m), j)
        pe_r = dp_obj.ext_chunk(dp_obj.intt_natural(phi), j)
        pie_r = dp_obj.ext_chunk(dp_obj.intt_natural(pi), j)
        uve_r = [dp_obj.ext_chunk(dp_obj.intt_natural(u), j) for u in uv]
        t_res = dp_obj.quotient_chunk(j, we_r, ze_r, me_r, pe_r, pie_r,
                                      uve_r, ch_r)
        res = ptpu.download_std(t_res)
        for fused in ("1", "0"):
            # the env var is LATCHED per DeviceProver at __init__ (one
            # prove = one t-chunk storage form); flip the latch itself
            # to exercise both modes on the same provers
            dp_stream.fused_quotient = fused == "1"
            dp_fixed.fused_quotient = fused == "1"
            t_str = dp_stream.quotient_chunk(j, we_r, ze_r, me_r, pe_r,
                                             pie_r, uve_r, ch_s)
            # partial ("fixed") residency: resident packed fixed
            # tables, streamed σ chains — same bits again
            t_fix = dp_fixed.quotient_chunk(j, we_r, ze_r, me_r, pe_r,
                                            pie_r, uve_r, ch_f)
            assert (t_str.dtype == np.uint16) == (fused == "1")
            assert (t_fix.dtype == np.uint16) == (fused == "1")
            assert np.array_equal(res, ptpu.download_std(t_str))
            assert np.array_equal(res, ptpu.download_std(t_fix))


def test_fused_quotient_latched_and_fused_intt_warns(dp, monkeypatch):
    """PTPU_FUSED_QUOTIENT is read ONCE at __init__ — a mid-prove env
    flip must not change the latched mode (one prove, one t-chunk
    storage form). PTPU_FUSED_INTT=1 on a full-residency prover is
    streaming-only and must warn once instead of silently ignoring the
    measurement flag (ADVICE r5)."""
    dp_obj, fixed_u64, sigma_u64 = dp
    monkeypatch.setenv("PTPU_FUSED_QUOTIENT", "0")
    dp2 = ptpu.DeviceProver(K, SHIFT, fixed_u64, sigma_u64,
                            ext_resident=False)
    assert dp2.fused_quotient is False
    monkeypatch.setenv("PTPU_FUSED_QUOTIENT", "1")
    assert dp2.fused_quotient is False  # latched, not re-read per chunk
    assert dp_obj.fused_quotient is True  # fixture built under the default

    monkeypatch.setenv("PTPU_FUSED_INTT", "1")
    monkeypatch.setattr(ptpu, "_FUSED_INTT_WARNED", False)
    with pytest.warns(UserWarning, match="streaming-only"):
        dp3 = ptpu.DeviceProver(K, SHIFT, fixed_u64, sigma_u64,
                                ext_resident=True)
    assert ptpu._FUSED_INTT_WARNED
    del dp2, dp3


def test_prove_streaming_mode_bytes_equal_host(tiny, monkeypatch):
    """Full prove_fast_tpu in streaming (k≥21-style) mode — packed
    coefficient arrays, on-the-fly pk ext chunks, packed t chunks,
    fused quotient AND the opt-in fused 4n inverse — must still emit
    byte-identical proofs to the host prover."""
    import random

    monkeypatch.setenv("PTPU_FUSED_INTT", "1")

    from protocol_tpu.utils.fields import BN254_FR_MODULUS as R
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk.plonk import FIXED_NAMES, NUM_WIRES, verify

    params, pk, cs = tiny
    ext_n = (1 << pk.k) * 4
    shift = _find_coset_shifts(ext_n, 2)[1]
    dp_stream = ptpu.DeviceProver(
        pk.k, shift,
        [pk.fixed_limbs[i] for i in range(len(FIXED_NAMES))],
        [pk.sigma_limbs[w] for w in range(NUM_WIRES)],
        ext_resident=False)
    pf._DEVICE_PROVERS.insert(0, (pk, dp_stream))
    try:
        r1, r2 = random.Random(4), random.Random(4)
        p_stream = pf.prove_fast_tpu(params, pk, cs,
                                     randint=lambda: r1.randrange(R))
        p_host = pf.prove_fast(params, pk, cs,
                               randint=lambda: r2.randrange(R))
    finally:
        pf._DEVICE_PROVERS[:] = [e for e in pf._DEVICE_PROVERS
                                 if e[0] is not pk]
    assert p_stream == p_host
    assert verify(params, pk, cs.public_values(), p_stream)


def test_quotient_chunk_matches_host(dp):
    dp_obj, fixed_u64, sigma_u64 = dp
    rng = np.random.default_rng(21)
    wires_u64 = [_rand_u64(N, 300 + w)[0] for w in range(6)]
    z_u64 = _rand_u64(N, 400)[0]
    m_u64 = _rand_u64(N, 401)[0]
    phi_u64 = _rand_u64(N, 402)[0]
    pi_u64 = _rand_u64(N, 403)[0]
    uv_u64 = [_rand_u64(N, 404 + i)[0] for i in range(4)]
    beta, gamma, beta_lk, alpha = [int(x) % P for x in
                                   rng.integers(1, 2**62, 4)]
    shifts = _find_coset_shifts(N, 6)

    # host ext arrays + quotient
    fk = native.FieldKernel(P)
    de = EvaluationDomain(K + 2)
    d = EvaluationDomain(K)

    def host_ext(c):
        return _host_ext(c)

    wires_e = np.stack([host_ext(c) for c in wires_u64])
    uv_e = np.stack([host_ext(c) for c in uv_u64])
    z_e = host_ext(z_u64)
    zw_c = z_u64.copy(); fk.coset_scale(zw_c, d.omega)
    zw_e = host_ext(zw_c)
    m_e = host_ext(m_u64)
    phi_e = host_ext(phi_u64)
    phw_c = phi_u64.copy(); fk.coset_scale(phw_c, d.omega)
    phiw_e = host_ext(phw_c)
    fixed_coeffs = []
    for c in fixed_u64:
        cc = c.copy(); fk.ntt(cc, d.omega, inverse=True)
        fixed_coeffs.append(cc)
    sigma_coeffs = []
    for c in sigma_u64:
        cc = c.copy(); fk.ntt(cc, d.omega, inverse=True)
        sigma_coeffs.append(cc)
    fixed_e = np.stack([host_ext(c) for c in fixed_coeffs])
    sigma_e = np.stack([host_ext(c) for c in sigma_coeffs])
    pi_c = pi_u64.copy(); fk.ntt(pi_c, d.omega, inverse=True)
    pi_e = host_ext(pi_c)

    xs = np.zeros((EXT_N, 4), dtype="<u8")
    xs[:, 0] = 1
    shift_arr = np.frombuffer(int(SHIFT).to_bytes(32, "little"), dtype="<u8")
    xs[:] = shift_arr
    fk.coset_scale(xs, de.omega)
    w4 = pow(de.omega, N, P)
    shift_n = pow(SHIFT, N, P)
    zh4 = [(shift_n * pow(w4, i, P) - 1) % P for i in range(4)]
    zh4_inv = [pow(v, -1, P) for v in zh4]
    reps = EXT_N // 4
    zh_inv = np.tile(native.ints_to_limbs(zh4_inv), (reps, 1))
    zh_tiled = np.tile(native.ints_to_limbs(zh4), (reps, 1))
    l0_den = fk.scalar_mul(fk.scalar_sub(xs, 1), N % P)
    fk.batch_inverse(l0_den)
    l0 = fk.vec_mul(zh_tiled, l0_den)

    t_host = fk.quotient_eval(wires_e, z_e, zw_e, m_e, phi_e, phiw_e,
                              uv_e, fixed_e, sigma_e, pi_e, xs, zh_inv,
                              l0, beta, gamma, beta_lk, alpha, shifts)

    # device: per-chunk quotient from the same inputs (polys degree < n,
    # no blinds here — blinding correctness is covered separately)
    wires_dev = [dp_obj.ext_chunks(ptpu.upload_mont(c)) for c in wires_u64]
    z_dev = dp_obj.ext_chunks(ptpu.upload_mont(z_u64))
    m_dev = dp_obj.ext_chunks(ptpu.upload_mont(m_u64))
    phi_dev = dp_obj.ext_chunks(ptpu.upload_mont(phi_u64))
    pi_dev = dp_obj.ext_chunks(ptpu.upload_mont(pi_c))
    uv_dev = [dp_obj.ext_chunks(ptpu.upload_mont(c)) for c in uv_u64]

    ch_planes = dp_obj.challenge_planes(beta, gamma, beta_lk, alpha,
                                        shifts)
    t_dev = []
    for j in range(4):
        t_dev.append(dp_obj.quotient_chunk(
            j, [w[j] for w in wires_dev], z_dev[j], m_dev[j], phi_dev[j],
            pi_dev[j], [u[j] for u in uv_dev], ch_planes))
    got = _chunks_to_host_order(dp_obj, t_dev)
    assert np.array_equal(got, t_host)
