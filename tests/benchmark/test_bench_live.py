"""The live cell's ``correct`` against its control and its faults.

Each run drives the whole of a live cell of ``attest-s19`` (its
configuration and the ``live`` traffic, as the cell ``attest-s19.live``
would name them) but for the chip check (the device is given, the CPU
stands in) at a tiny size: a scale-8
Kronecker graph, 8 signers, 4 attestations a second for a 1.5 s window
after half a second of stream and before at most 5 s of tail, 50 reads
a second from the client process. Set-up restores the daemon from its snapshot, the window lands
and reads, then the final graph, every read and the final table are
compared, and the daemon's write-ahead log is read back. With no
accelerator the daemon recovers signers on the host,
so no ingest ladder compiles here. The sound program must pass; the
bfloat16 control and each fault planted in the timed path must come out
not correct."""

import numpy as np
import pytest

from benchmark import reference, run
from benchmark.runners import live

SEED = 2**31 + 29
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
WINDOW_S = 1.5
END_TO_END = [{"name": "setup_s", "unit": "s"},
              {"name": "freshness_p50_s", "unit": "s"},
              {"name": "read_p50_ms", "unit": "ms"}]


def live_spec() -> dict:
    """What ``run.cell_spec`` would give for ``attest-s19.live``."""
    return {"cell": {"name": "attest-s19.live", "config": "attest-s19",
                     "traffic": "live", "chips": 1},
            "config": run.load_json(run.HERE, "configs", "attest-s19.json"),
            "traffic": run.load_json(run.HERE, "traffic", "live.json"),
            "end_to_end": END_TO_END, "per_layer": []}


@pytest.fixture
def spec(monkeypatch):
    from protocol_tpu.utils import trace

    monkeypatch.setattr(trace.TRACER, "enabled", trace.TRACER.enabled)
    monkeypatch.setattr(live, "prepared_seconds", lambda: WINDOW_S)
    s = live_spec()
    s["config"].update(scale=8, signers=8)
    s["config"]["service"].update(poll_interval=0.1, refresh_interval=0.1)
    s["traffic"].update(attest_rate=4, stream_s=0.5, tail_s=5,
                        drain_wait_s=10)
    return s


def run_once(spec, seed=SEED):
    return run.run_cell(spec, seed, WINDOW_S, False, dict(DEVICE))


def spans(*names):
    from protocol_tpu.utils import trace

    got = trace.summary()
    return {n: got.get(n, {}).get("count", 0) for n in names}


def test_sound_run_is_correct(spec):
    before = spans("service.recover_scalar", "ingest.recover_batch")
    out = run_once(spec)
    after = spans("service.recover_scalar", "ingest.recover_batch")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 6 + 75
    assert set(out["checks"]) == {"rel_l1", "wrong_edges", "wrong_reads",
                                  "unlogged", "wrong_hashes"}
    assert out["checks"]["rel_l1"]["value"] < spec["config"]["checks"][
        "rel_l1"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "freshness_p50_s",
                                   "read_p50_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    # host recovery, no device ladder
    assert after["service.recover_scalar"] > before["service.recover_scalar"]
    assert after["ingest.recover_batch"] == before["ingest.recover_batch"]


def refresher_publishes(monkeypatch, change):
    """Plant ``change(refresher, scores) -> scores`` where the daemon
    publishes a table."""
    from protocol_tpu.service.refresh import ScoreRefresher

    original = ScoreRefresher._publish

    def _publish(self, addresses, scores, n, *args):
        return original(self, addresses, change(self, scores), n, *args)

    monkeypatch.setattr(ScoreRefresher, "_publish", _publish)


def bf16_control(monkeypatch, cfg):
    et = cfg["eigentrust"]

    def low(refresher, scores):
        n, src, dst, val, *_ = refresher.graph.snapshot()
        out, _ = reference.eigentrust_bf16(n, src, dst, val, et["alpha"],
                                           et["initial_score"], et["tol"],
                                           et["max_iterations"])
        return out

    refresher_publishes(monkeypatch, low)


def table_unchanged(monkeypatch, cfg):
    refresher_publishes(monkeypatch, lambda refresher, scores:
                        refresher.table.scores)


def sink_keeps(monkeypatch, keep):
    """Plant ``keep(calls, batch) -> batch`` where the daemon's sink
    takes a polled batch."""
    from protocol_tpu.service.daemon import TrustService

    original = TrustService._sink
    calls = []

    def _sink(self, batch, block, blocks=None):
        calls.append(len(batch))
        kept = keep(len(calls), list(batch))
        if blocks is not None:
            blocks = [blocks[batch.index(s)] for s in kept]
        return original(self, kept, block, blocks)

    monkeypatch.setattr(TrustService, "_sink", _sink)


def dropped_attestation(monkeypatch, cfg):
    # all but the first of the first batch: the stream's head lands at
    # once, so that batch holds at least its two attestations, and this
    # seed's first repeats a base edge's value (leaving it out alone
    # would change nothing)
    sink_keeps(monkeypatch, lambda call, batch:
               batch[:1] if call == 1 else batch)


def half_of_each_batch(monkeypatch, cfg):
    sink_keeps(monkeypatch, lambda call, batch: batch[::2])


def forged_signer(monkeypatch, cfg):
    from protocol_tpu.service import daemon

    original = daemon.recover_signers

    def recover_signers(batch, batched=None):
        signers = original(batch, batched=batched)
        return [b"\x13" * 20] + signers[1:] if signers else signers

    monkeypatch.setattr(daemon, "recover_signers", recover_signers)


def stale_read(monkeypatch, cfg):
    """``GET /score`` answers from the table published before the one
    whose revision it reports."""
    from protocol_tpu.service.refresh import ScoreRefresher, ScoreTable

    tables = []
    original = ScoreRefresher._publish

    def _publish(self, *args):
        tables.append(self.table)
        return original(self, *args)

    def score_of(self, addr):
        i = self._index.get(addr)
        older = [t for t in tables if t.revision < self.revision]
        if i is None or not older:
            return None if i is None else float(self.scores[i])
        return float(older[-1].scores[i])

    monkeypatch.setattr(ScoreRefresher, "_publish", _publish)
    monkeypatch.setattr(ScoreTable, "score_of", score_of)


def skipped_wal_append(monkeypatch, cfg):
    """The first batch after the restore goes unlogged."""
    from protocol_tpu.store.wal import AttestationWAL

    original = AttestationWAL.append
    calls = []

    def append(self, records):
        calls.append(len(records))
        if len(calls) == 1:
            return self.position()
        return original(self, records)

    monkeypatch.setattr(AttestationWAL, "append", append)


def wal_not_fsynced(monkeypatch, cfg):
    cfg["service"]["wal_fsync"] = "never"


def wrong_device_hash(monkeypatch, cfg):
    """The hash that the traffic's fifth attestation is signed over is
    off by one, as a faulty Poseidon kernel would make it."""
    from benchmark import signer

    original = signer.attestation_hashes

    def attestation_hashes(atts):
        out = original(atts)
        out[4] += 1
        return out

    monkeypatch.setattr(signer, "attestation_hashes", attestation_hashes)


@pytest.mark.parametrize("fault, caught_by", [
    (bf16_control, "rel_l1"), (table_unchanged, "rel_l1"),
    (dropped_attestation, "wrong_edges"), (half_of_each_batch, "wrong_edges"),
    (forged_signer, "wrong_edges"), (stale_read, "wrong_reads"),
    (skipped_wal_append, "unlogged"), (wal_not_fsynced, "unlogged"),
    (wrong_device_hash, "wrong_hashes")])
def test_fault_is_not_correct(spec, monkeypatch, fault, caught_by):
    fault(monkeypatch, spec["config"])
    out = run_once(spec)
    assert out["correct"] is False
    check = out["checks"][caught_by]
    assert check["value"] > check["limit"]


def traffic(spec, seed):
    return live.Traffic(spec["config"], spec["traffic"], seed, WINDOW_S)


def test_same_seed_same_schedule(spec):
    a, b = traffic(spec, SEED), traffic(spec, SEED)
    for (pa, xa), (pb, xb) in zip(a.batches(), b.batches()):
        assert pa == pb
        for u, v in zip(xa, xb):
            np.testing.assert_array_equal(u, v)
    np.testing.assert_array_equal(a.reads, b.reads)
    assert a.addresses == b.addresses and a.nonces == b.nonces
    c = traffic(spec, SEED + 1)
    assert a.addresses != c.addresses
    assert not np.array_equal(a.reads, c.reads)
    assert [len(p[0]) for _, p in a.batches()] == [
        len(p[0]) for _, p in c.batches()]


def test_edge_differences_counts_each_kind():
    s, d, v = (np.array(x) for x in ([0, 0, 1], [1, 2, 2], [5., 6., 7.]))
    assert live.edge_differences(s, d, v, s, d, v) == 0
    assert live.edge_differences(s, d, v, s, d, v + [0, 0, 1]) == 1
    assert live.edge_differences(s, d, v, s[:2], d[:2], v[:2]) == 1
    assert live.edge_differences(s, d, v, np.array([0, 0, 2]), d, v) == 2


def test_zipf_ranks_skew():
    rng = np.random.default_rng(1)
    ranks = live.zipf_ranks(rng, 1000, 0.99, 20000)
    counts = np.bincount(ranks, minlength=1000)
    assert ranks.min() >= 0 and ranks.max() < 1000
    assert counts[0] > 5 * counts[10] > 0
