"""Batched multi-column commit engine + native g1_msm_multi tests.

The engine contract (zk/commit_engine.py): columns grouped into one
``g1_msm_multi`` window pass must be BIT-EXACT per column against the
serial ``g1_msm`` oracle for any grouping, any column content, and any
flip pattern; proofs must be byte-identical with the engine on or off
on both prove paths (pinned blinding); fetch-backed items must resolve
in submission order with errors surfaced, not swallowed.
"""

import random
import threading

import numpy as np
import pytest

from protocol_tpu import native

if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

from protocol_tpu.utils import trace  # noqa: E402
from protocol_tpu.utils.fields import BN254_FR_MODULUS as R  # noqa: E402
from protocol_tpu.zk.bn254 import (  # noqa: E402
    BN254_FQ_MODULUS as Q,
    G1_GEN,
    g1_neg,
)
from protocol_tpu.zk.commit_engine import (  # noqa: E402
    CommitEngine,
    balance_columns,
)


def _bases(n, seed):
    rng = random.Random(seed)
    sc = native.ints_to_limbs([rng.randrange(1, R) for _ in range(n)])
    return native.g1_fixed_base_muls(Q, G1_GEN, sc)


def _cols(kcols, n, seed):
    rng = random.Random(seed)
    return np.stack([
        native.ints_to_limbs([rng.randrange(0, R) for _ in range(n)])
        for _ in range(kcols)])


# --- native kernel parity ---------------------------------------------------

def test_msm_multi_matches_serial_oracle():
    """Random and adversarial columns, several (n, K) shapes, identity
    bases mixed in — every column of one g1_msm_multi call equals its
    serial g1_msm twin bit-for-bit."""
    for n, kcols, seed in ((1, 1, 1), (33, 3, 2), (300, 5, 3),
                           (1200, 2, 4)):
        pts = _bases(n, seed)
        if n > 100:
            pts[::7] = 0  # identity rows must be skipped per column
        cols = _cols(kcols, n, seed + 50)
        if kcols >= 3:
            cols[0][:] = 0                      # all-zero column
            cols[1][:] = 0
            cols[1][: n // 2, 0] = 1            # 0/1 selector column
            cols[2] = native.ints_to_limbs([R - 1] * n)  # dense −1
        got = native.g1_msm_multi(Q, pts, cols)
        want = [native.g1_msm(Q, pts, cols[k]) for k in range(kcols)]
        assert got == want, (n, kcols)


def test_msm_multi_flips_negate_bases_per_column():
    """flips[k, i] commits column k against −P_i — the shared-base form
    of _msm_signed's per-call y negation."""
    n, kcols = 64, 3
    pts = _bases(n, 7)
    cols = _cols(kcols, n, 8)
    flips = np.zeros((kcols, n), dtype=np.uint8)
    flips[0, ::3] = 1
    flips[2, : n // 2] = 1
    got = native.g1_msm_multi(Q, pts, cols, flips)
    vals = native.limbs_to_ints(pts.reshape(-1, 4))
    for k in range(kcols):
        negd = []
        for i in range(n):
            p = (vals[2 * i], vals[2 * i + 1])
            negd.append(g1_neg(p) if flips[k, i] else p)
        want = native.g1_msm(Q, native.points_to_limbs(negd), cols[k])
        assert got[k] == want, k


def test_msm_multi_cancellation_to_identity():
    pts = native.g1_fixed_base_muls(Q, G1_GEN, native.ints_to_limbs([5, 5]))
    cols = np.stack([native.ints_to_limbs([3, R - 3]),
                     native.ints_to_limbs([7, 9])])
    got = native.g1_msm_multi(Q, pts, cols)
    assert got[0] is None
    assert got[1] == native.g1_msm(Q, pts, cols[1])


def test_balance_columns_preserves_commitment():
    """balanced + flips == original column, semantically: s·P for
    s ≥ (R+1)/2 becomes (R−s)·(−P). balance_columns OWNS its input
    (in-place, no defensive copy at ~450 MB/flush scale), so the call
    hands it a private copy the way the engine's np.stack does."""
    n = 128
    pts = _bases(n, 11)
    cols = _cols(2, n, 12)
    cols[1][:3] = native.ints_to_limbs([R - 1, (R + 1) // 2, R - 12345])
    balanced, flips = balance_columns(cols.copy())
    got = native.g1_msm_multi(Q, pts, balanced, flips)
    want = [native.g1_msm(Q, pts, cols[k]) for k in range(2)]
    assert got == want
    assert flips[1, :3].all()  # the near-R rows flipped


# --- engine scheduling ------------------------------------------------------

def test_random_k_groupings_match_commit_limbs(monkeypatch):
    """Property test: 10 columns of two different lengths, submitted in
    random order across random flush splits, commit identically to the
    serial ``commit_limbs`` oracle — grouping is an optimization, never
    semantics."""
    from protocol_tpu.zk import prover_fast as pf

    params = pf.setup_params_fast(8, seed=b"grouping")
    rng = random.Random(99)
    n = 1 << 8
    lens = [n if i % 3 else n // 2 for i in range(10)]
    cols = [np.ascontiguousarray(_cols(1, ln, 20 + i)[0])
            for i, ln in enumerate(lens)]
    oracle = [pf.commit_limbs(params, c) for c in cols]
    for _ in range(3):
        order = rng.sample(range(10), 10)
        got = {}
        idx = 0
        while idx < len(order):
            take = rng.randrange(1, 5)
            chunk = order[idx : idx + take]
            idx += take
            eng = CommitEngine(params)
            for i in chunk:
                eng.submit_coeffs(f"col{i}", cols[i])
            for i, pt in zip(chunk, eng.flush()):
                got[i] = pt
        assert [got[i] for i in range(10)] == oracle


def test_fetch_items_overlap_and_keep_submission_order():
    """Fetch-backed columns resolve on the background thread in
    submission order; flush() returns points in submission order even
    when ready-ness arrives out of phase with concrete items."""
    from protocol_tpu.zk import prover_fast as pf

    params = pf.setup_params_fast(8, seed=b"fetch")
    n = 1 << 8
    cols = [np.ascontiguousarray(_cols(1, n, 40 + i)[0])
            for i in range(4)]
    oracle = [pf.commit_limbs(params, c) for c in cols]
    gate = threading.Event()

    def slow_fetch(i):
        def fetch():
            gate.wait(5.0)
            return cols[i]
        return fetch

    eng = CommitEngine(params)
    eng.submit_coeffs("f0", fetch=slow_fetch(0))
    eng.submit_coeffs("c1", cols[1])
    eng.submit_coeffs("f2", fetch=slow_fetch(2))
    eng.submit_coeffs("c3", cols[3])
    gate.set()
    assert eng.flush() == oracle


def test_fetch_error_propagates():
    from protocol_tpu.zk import prover_fast as pf

    params = pf.setup_params_fast(8, seed=b"fetcherr")

    def boom():
        raise RuntimeError("device lost")

    eng = CommitEngine(params)
    eng.submit_coeffs("bad", fetch=boom)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.flush()


# --- intra-prove shards (addressable work units + rendezvous) ---------------

class _ThreadRunner:
    """Minimal zk/shards.py runner: executes dispatched units on a
    side thread in REVERSE submission order — the adversarial
    completion order the rendezvous must absorb back into submission
    order."""

    fanout = 3

    def __init__(self):
        self.threads = []
        self.executed = 0

    def dispatch(self, units):
        def run_all(us):
            for u in us:
                u.claimed = True
                u.run()
                self.executed += 1

        t = threading.Thread(target=run_all,
                             args=(list(reversed(units)),), daemon=True)
        t.start()
        self.threads.append(t)

    def rendezvous(self, units):
        for u in units:
            assert u.done.wait(30), "unit never completed"
        err = next((u.error for u in units if u.error is not None),
                   None)
        if err is not None:
            raise err


def test_sharded_flush_keeps_submission_order():
    """Under a shard runner, flush() splits groups into units executed
    out of order on another thread — points must still come back in
    submission order, bit-exact vs the serial oracle."""
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk import shards

    params = pf.setup_params_fast(8, seed=b"shard-order")
    n = 1 << 8
    cols = [np.ascontiguousarray(_cols(1, n, 70 + i)[0])
            for i in range(7)]
    oracle = [pf.commit_limbs(params, c) for c in cols]
    runner = _ThreadRunner()
    with shards.shard_scope(runner):
        eng = CommitEngine(params)
        for i, c in enumerate(cols):
            eng.submit_coeffs(f"col{i}", c)
        got = eng.flush()
    assert got == oracle
    assert runner.executed >= 2, "the group never split into units"


def test_flush_async_rendezvous_under_device_window():
    """flush_async dispatches materialized groups NOW; result() is the
    deterministic merge point — the caller can hold a device-occupancy
    window in between and the units compute under it."""
    import time as _time

    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk import shards

    params = pf.setup_params_fast(8, seed=b"shard-async")
    n = 1 << 8
    cols = [np.ascontiguousarray(_cols(1, n, 90 + i)[0])
            for i in range(6)]
    oracle = [pf.commit_limbs(params, c) for c in cols]
    runner = _ThreadRunner()
    with shards.shard_scope(runner):
        eng = CommitEngine(params)
        for i, c in enumerate(cols):
            eng.submit_coeffs(f"col{i}", c)
        handle = eng.flush_async()
        assert handle.units, "materialized groups were not dispatched"
        _time.sleep(0.05)  # the device-occupancy stand-in
        assert handle.result() == oracle
        assert handle.result() == oracle  # idempotent
    assert runner.executed >= 2, "pre-dispatch never split into units"


def test_sharded_flush_surfaces_unit_errors():
    from protocol_tpu.zk import prover_fast as pf
    from protocol_tpu.zk import shards

    params = pf.setup_params_fast(8, seed=b"shard-err")
    bad = np.zeros((1 << 8, 5), dtype="<u8")  # wrong limb shape
    runner = _ThreadRunner()
    with shards.shard_scope(runner):
        eng = CommitEngine(params)
        eng.submit_coeffs("a", _cols(1, 1 << 8, 99)[0])
        eng.submit_coeffs("b", bad)
        with pytest.raises(Exception):
            eng.flush()


# --- byte-identical proofs, engine on vs off -------------------------------

@pytest.fixture(scope="module")
def tiny():
    """One tiny circuit, its k=7 params and its eval-form key, shared by
    the proving tests below (one set of prover programs per file)."""
    from protocol_tpu.cli.profilecmd import synthetic_circuit
    from protocol_tpu.zk import prover_fast as pf

    cs = synthetic_circuit(gates=24, seed=5, lookup_row=True)
    params = pf.setup_params_fast(7, seed=b"engine-parity")
    return pf, params, pf.keygen_fast(params, cs, k=7, eval_pk=True), cs


def test_engine_on_off_proofs_identical_host(tiny, monkeypatch):
    from protocol_tpu.zk.plonk import verify

    pf, params, pk, cs = tiny
    monkeypatch.delenv("PTPU_COMMIT_ENGINE", raising=False)
    on = pf.prove_fast(params, pk, cs, randint=lambda: 424242)
    monkeypatch.setenv("PTPU_COMMIT_ENGINE", "0")
    off = pf.prove_fast(params, pk, cs, randint=lambda: 424242)
    assert on == off
    assert verify(params, pk, cs.public_values(), on)


def test_engine_on_off_proofs_identical_tpu(tiny, monkeypatch):
    pytest.importorskip("jax")
    from protocol_tpu.zk.plonk import verify

    pf, params, pk, cs = tiny
    monkeypatch.delenv("PTPU_COMMIT_ENGINE", raising=False)
    on = pf.prove_fast_tpu(params, pk, cs, randint=lambda: 171717)
    monkeypatch.setenv("PTPU_COMMIT_ENGINE", "0")
    off = pf.prove_fast_tpu(params, pk, cs, randint=lambda: 171717)
    host = pf.prove_fast(params, pk, cs, randint=lambda: 171717)
    assert on == off == host
    assert verify(params, pk, cs.public_values(), on)


# --- observability ----------------------------------------------------------

def test_commit_stages_and_batch_histogram(tiny, monkeypatch):
    """A host prove lands commit.* stage series carrying the batched
    label and populates ptpu_commit_batch_size with widths > 1 (the
    r1 batch is 7 same-bases columns)."""
    pf, params, pk, cs = tiny
    monkeypatch.delenv("PTPU_COMMIT_ENGINE", raising=False)
    trace.enable()
    trace.TRACER.reset_instruments()
    try:
        pf.prove_fast(params, pk, cs, randint=lambda: 7)
        stages = {}
        for items, s in trace.histogram("prover_stage_seconds").series():
            labels = dict(items)
            if labels.get("stage", "").startswith("commit."):
                stages[labels["stage"]] = labels
        assert {"commit.r1", "commit.r2", "commit.t",
                "commit.open"} <= set(stages)
        assert all(lbl.get("batched") == "1" for lbl in stages.values())
        widths = trace.histogram("commit_batch_size").series()
        assert widths, "no commit batch sizes recorded"
        total = sum(s["count"] for _, s in widths)
        mean = sum(s["sum"] for _, s in widths) / total
        assert mean > 1.0, mean
    finally:
        trace.TRACER.reset_instruments()
        trace.disable()
