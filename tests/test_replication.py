"""Read-path scale-out tests (PR 13): follower replicas over shipped
WAL segments, signed score bundles, and the ETag'd read endpoints.

Determinism note: the leader and follower configs force every refresh
COLD (``cold_edit_fraction=0``) — cold converge from uniform on the
same graph is bit-deterministic on one box, which is what lets these
tests assert the follower's ``/scores`` BYTE-equal to the leader's at
the same WAL position (the acceptance criterion), not merely within
tolerance. Warm-started replicas agree within tol; byte equality is
the assertable contract when the refresh trajectory is pinned.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np  # noqa: F401 - fixtures build numpy state
import pytest

jax = pytest.importorskip("jax")

from protocol_tpu.client import Client, ClientConfig  # noqa: E402
from protocol_tpu.client.chain import RpcChain  # noqa: E402
from protocol_tpu.client.eth import (  # noqa: E402
    address_from_public_key,
    ecdsa_keypairs_from_mnemonic,
)
from protocol_tpu.client.mocknode import MockNode  # noqa: E402
from protocol_tpu.service import (  # noqa: E402
    FaultInjector,
    FollowerService,
    ServiceConfig,
    TrustService,
)
from protocol_tpu.utils.errors import EigenError  # noqa: E402

MNEMONIC = "test test test test test test test test test test test junk"
DOMAIN = b"\x00" * 20


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, dict(resp.headers), resp.read()


def _get_json(url, headers=None):
    return json.loads(_get(url, headers)[2])


def _wait(pred, timeout=60.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture()
def devnet():
    node = MockNode()
    url = node.start()
    yield node, url
    node.stop()


def _cfg(**over):
    base = dict(port=0, poll_interval=0.05, refresh_interval=0.05,
                tol=1e-10, backoff_base=0.05, backoff_max=0.2,
                drain_timeout=10.0, snapshot_every=4,
                # every refresh cold: bit-deterministic across leader
                # and follower (see module docstring)
                cold_edit_fraction=0.0)
    base.update(over)
    return ServiceConfig(**base)


def _leader(tmp_path, node_url, **over):
    deployer = ecdsa_keypairs_from_mnemonic(MNEMONIC, 1)[0]
    chain = RpcChain.deploy_signed(node_url, deployer)
    config = ClientConfig(
        as_address="0x" + chain.contract_address.hex(),
        node_url=node_url, domain="0x" + DOMAIN.hex())
    client = Client(config, MNEMONIC)
    svc = TrustService(
        client, _cfg(**over), str(tmp_path / "cursor"),
        provers={"echo": lambda params: {"echo": params}},
        faults=FaultInjector({"rpc": 0.0, "device": 0.0, "disk": 0.0}),
        state_dir=str(tmp_path / "leader-state"))
    return svc, client


def _follower(tmp_path, leader_url, name="fstate", **over):
    return FollowerService(
        leader_url, DOMAIN, _cfg(**over), str(tmp_path / name),
        faults=FaultInjector({"rpc": 0.0, "device": 0.0, "disk": 0.0}))


def _hard_kill_follower(fol):
    """Simulate SIGKILL: stop threads with NO drain, NO farewell
    snapshot, NO final cursor persist — only per-poll persistence
    survives, the crash contract the follower claims."""
    fol._stop.set()
    fol._dirty.set()
    for t in fol._threads:
        t.join(timeout=10)
    fol._server.shutdown()
    fol._server.server_close()
    fol.store.close()


def _attest_pairs(client, kps, pairs):
    for i, about, value in pairs:
        client.keypairs[0] = kps[i]
        client.attest(about, value)


def _settled(url, min_edges=0, min_attestations=0):
    """The leader has ingested ``min_attestations`` (latest-wins churn
    leaves the edge count flat, so edges alone cannot say a batch
    landed) and published its latest revision."""
    st = _get_json(url + "/status")
    return (st["graph"]["edges"] >= min_edges
            and st["tailer"]["attestations"] >= min_attestations
            and st["last_refresh"]["revision"]
            == st["graph"]["revision"])


def _follower_caught_up(furl, lurl):
    """Same WAL coverage + both published their own latest revision.
    (Graph revisions are NODE-LOCAL batch counters — one shipped chunk
    can fold several leader batches into one apply — so equality is on
    WAL position, never on revision numbers.)"""
    fs = _get_json(furl + "/status")
    ls = _get_json(lurl + "/status")
    return (fs["repl"]["cursor"] == ls["store"]["wal_position"]
            and fs["last_refresh"]["revision"] == fs["graph"]["revision"]
            and ls["last_refresh"]["revision"]
            == ls["graph"]["revision"])


# --- bundle codec ------------------------------------------------------------


def test_bundle_codec_roundtrip_and_tamper_rejection():
    """Canonical encode → RFC 6979 sign → recover-verify round-trip;
    any mutated payload byte, a mutated signature, and a pinned-leader
    mismatch must all be rejected; signing is deterministic (the ETag
    contract)."""
    import hashlib

    from protocol_tpu.service.bundle import (
        bundle_json,
        decode_bundle_payload,
        encode_bundle_payload,
        sign_bundle,
        verify_bundle,
    )

    kp, other = ecdsa_keypairs_from_mnemonic(MNEMONIC, 2)
    leader = address_from_public_key(kp.public_key)
    digest = hashlib.sha256(b"scores").digest()
    payload = encode_bundle_payload(leader, 42, (7, 4096), digest,
                                    1000, 1234.5, "job-17")
    assert sign_bundle(kp, payload) == sign_bundle(kp, payload)
    sig = sign_bundle(kp, payload)
    fields = verify_bundle(payload, sig, leader)
    assert fields["revision"] == 42
    assert fields["wal_position"] == (7, 4096)
    assert fields["score_digest"] == digest
    assert fields["et_proof_id"] == "job-17"
    assert decode_bundle_payload(payload)["n_scores"] == 1000
    body = bundle_json(payload, sig)
    assert bytes.fromhex(body["payload"]) == payload
    # tamper every region: magic, leader, fixed fields, digest, id
    for k in (0, 12, 35, 60, len(payload) - 1):
        bad = bytearray(payload)
        bad[k] ^= 1
        with pytest.raises(EigenError):
            verify_bundle(bytes(bad), sig, leader)
    badsig = bytearray(sig)
    badsig[3] ^= 1
    with pytest.raises(EigenError):
        verify_bundle(payload, bytes(badsig), leader)
    # a bundle signed by someone else under this leader's name
    forged = sign_bundle(other, payload)
    with pytest.raises(EigenError):
        verify_bundle(payload, forged, leader)
    # pinning a different expected leader
    with pytest.raises(EigenError):
        verify_bundle(payload, sig,
                      address_from_public_key(other.public_key))


# --- ETags -------------------------------------------------------------------


def test_scores_etag_304_and_invalidation(tmp_path, devnet):
    """/scores and /score/<addr> carry a strong revision-derived ETag:
    If-None-Match revalidation costs a 304 (no body), and new churn
    invalidates it — the cheap read-path win independent of
    replication."""
    _, node_url = devnet
    svc, client = _leader(tmp_path, node_url)
    url = svc.start()
    try:
        kps = ecdsa_keypairs_from_mnemonic(MNEMONIC, 2)
        addrs = [address_from_public_key(k.public_key) for k in kps]
        _attest_pairs(client, kps, [(0, addrs[1], 7), (1, addrs[0], 9)])
        _wait(lambda: _settled(url, min_edges=2), what="leader settle")
        status, h, body = _get(url + "/scores")
        etag = h["ETag"]
        assert status == 200 and etag.startswith('"sc-')
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + "/scores", headers={"If-None-Match": etag})
        assert ei.value.code == 304
        assert ei.value.headers["ETag"] == etag
        s2, h2, _ = _get(url + f"/score/0x{addrs[0].hex()}")
        assert h2["ETag"] == etag  # one table, one validator
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + f"/score/0x{addrs[0].hex()}",
                 headers={"If-None-Match": etag})
        assert ei.value.code == 304
        # churn invalidates: a new revision must serve 200 + new ETag
        rev0 = _get_json(url + "/status")["graph"]["revision"]
        _attest_pairs(client, kps, [(0, addrs[1], 11)])
        _wait(lambda: _settled(url, min_attestations=3),
              what="revision bump published")
        assert _get_json(url + "/status")["graph"]["revision"] > rev0
        status, h3, _ = _get(url + "/scores",
                             headers={"If-None-Match": etag})
        assert status == 200 and h3["ETag"] != etag
    finally:
        svc.shutdown()


# --- follower bootstrap + tail ----------------------------------------------


def test_follower_bootstrap_tail_byte_equality(tmp_path, devnet):
    """A follower bootstraps from the leader snapshot, tails the
    shipped WAL, and — at the same WAL position — serves a /scores
    page BYTE-equal to the leader's with the same ETag; the leader's
    /status repl section shows it at eof; /bundle flows through
    verbatim and verifies against the leader address; the write
    surface is closed (503/404)."""
    _, node_url = devnet
    svc, client = _leader(tmp_path, node_url)
    url = svc.start()
    fol = None
    try:
        kps = ecdsa_keypairs_from_mnemonic(MNEMONIC, 3)
        addrs = [address_from_public_key(k.public_key) for k in kps]
        _attest_pairs(client, kps, [(0, addrs[1], 7), (1, addrs[0], 9)])
        _wait(lambda: _settled(url, min_edges=2), what="leader settle")
        fol = _follower(tmp_path, url)
        furl = fol.start()
        # records PAST the bootstrap snapshot exercise the tail path
        _attest_pairs(client, kps,
                      [(0, addrs[2], 5), (2, addrs[0], 3),
                       (1, addrs[2], 4)])
        _wait(lambda: _settled(url, min_edges=4, min_attestations=5),
              what="leader settle 2")
        _wait(lambda: _follower_caught_up(furl, url),
              what="follower catch-up")
        ls, lh, lbody = _get(url + "/scores")
        fs, fh, fbody = _get(furl + "/scores")
        lj, fj = json.loads(lbody), json.loads(fbody)
        # byte equality of the served CONTENT at the same WAL
        # position: every (address, score) pair identical — asserted
        # over the whole vector, not sampled. (revision/computed_at
        # are node-local publish bookkeeping; the ETag is accordingly
        # a per-node validator, standard HTTP semantics.)
        assert lj["scores"] == fj["scores"] and lj["scores"]
        from protocol_tpu.service.bundle import decode_bundle_payload

        # ... and the two tables' content digests agree (the bundle's
        # score_digest covers addresses + float64 score bytes)
        assert svc.refresher.table.digest == fol.refresher.table.digest
        # conditional read against the follower with ITS OWN etag
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(furl + "/scores", headers={"If-None-Match": fh["ETag"]})
        assert ei.value.code == 304
        # leader sees the follower at eof
        repl = _get_json(url + "/status")["repl"]
        assert repl["followers"] and repl["followers"][0]["eof"]
        assert repl["followers"][0]["follower"] == fol.follower_id
        # per-replica gauges live and sane
        fstat = _get_json(furl + "/status")
        assert fstat["repl"]["lag_records"] == 0
        assert 0.0 <= fstat["repl"]["lag_seconds"] < 30.0
        assert fstat["score_freshness_seconds"] < 120.0
        metrics = _get(furl + "/metrics")[2].decode()
        assert "ptpu_repl_lag_records" in metrics
        assert "ptpu_repl_lag_seconds" in metrics
        assert "ptpu_repl_poll_seconds" in metrics
        # the signed bundle: served verbatim, verifies as the leader's
        from protocol_tpu.service.bundle import verify_bundle

        _, bh, bbody = _get(url + "/bundle")
        _wait(lambda: fol.bundle_response() is not None,
              what="follower bundle cache")
        fb = _get(furl + "/bundle")
        bd = json.loads(fb[2])
        fields = verify_bundle(bytes.fromhex(bd["payload"]),
                               bytes.fromhex(bd["signature"]))
        assert decode_bundle_payload(
            bytes.fromhex(bd["payload"]))["leader"] == fields["leader"]
        # ETag round-trip on the bundle
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(furl + "/bundle",
                 headers={"If-None-Match": fb[1]["ETag"]})
        assert ei.value.code == 304
        # read-only surface
        req = urllib.request.Request(
            furl + "/proofs", data=b'{"kind": "echo"}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(furl + "/proofs/job-1")
        assert ei.value.code == 404
    finally:
        if fol is not None:
            fol.shutdown()
        svc.shutdown()


def test_follower_kill_restart_resumes_from_cursor(tmp_path, devnet):
    """SIGKILL mid-tail → restart on the same state dir → the follower
    restores from its OWN local snapshot+WAL (no re-bootstrap, no
    re-ship of the history), resumes the leader tail from its
    persisted cursor, and converges back to byte-equal scores."""
    _, node_url = devnet
    svc, client = _leader(tmp_path, node_url)
    url = svc.start()
    fol2 = None
    try:
        kps = ecdsa_keypairs_from_mnemonic(MNEMONIC, 3)
        addrs = [address_from_public_key(k.public_key) for k in kps]
        _attest_pairs(client, kps, [(0, addrs[1], 7), (1, addrs[0], 9),
                                    (0, addrs[2], 2)])
        _wait(lambda: _settled(url, min_edges=3), what="leader settle")
        fol = _follower(tmp_path, url)
        furl = fol.start()
        _wait(lambda: _follower_caught_up(furl, url),
              what="follower catch-up")
        applied_before = fol.records_applied
        cursor_before = fol._cursor
        assert applied_before >= 1 or fol.graph.n_edges >= 3
        row_before = _get_json(url + "/status")["repl"][
            "followers"][0]["records_shipped"]
        _hard_kill_follower(fol)
        # churn while the follower is down
        _attest_pairs(client, kps, [(2, addrs[0], 6), (1, addrs[2], 8)])
        _wait(lambda: _settled(url, min_attestations=5),
              what="leader settle 2")
        fol2 = _follower(tmp_path, url)
        # the constructor restored local state BEFORE any network I/O:
        # same records, same cursor — its own cursor, not 0:0
        assert fol2.records_applied == applied_before
        assert fol2._cursor == cursor_before
        assert fol2.follower_id == fol.follower_id
        furl2 = fol2.start()
        _wait(lambda: _follower_caught_up(furl2, url),
              what="follower catch-up after restart")
        lbody = json.loads(_get(url + "/scores")[2])
        fbody = json.loads(_get(furl2 + "/scores")[2])
        assert lbody["scores"] == fbody["scores"]
        # catch-up shipped only the while-down records (+ at most one
        # refetched chunk) — never the pre-cursor history
        row_after = _get_json(url + "/status")["repl"][
            "followers"][0]["records_shipped"]
        assert row_after - row_before <= 4, (row_before, row_after)
        assert fol2.gaps == 0
    finally:
        if fol2 is not None:
            fol2.shutdown()
        svc.shutdown()


# --- compaction vs the ship floor -------------------------------------------


def test_leader_compaction_ship_floor(tmp_path, devnet):
    """WAL compaction defers while an ACTIVE follower is catching up
    (the ship floor), proceeds once it reaches eof — and a follower
    whose cursor predates a compaction re-tails the folded log from
    the earliest position with content dedup (gap recovery), ending
    byte-equal."""
    _, node_url = devnet
    svc, client = _leader(tmp_path, node_url,
                          wal_segment_bytes=256,
                          wal_compact_segments=2,
                          snapshot_every=10_000)
    url = svc.start()
    fol2 = None
    try:
        kps = ecdsa_keypairs_from_mnemonic(MNEMONIC, 3)
        addrs = [address_from_public_key(k.public_key) for k in kps]
        rounds = [(i % 3, addrs[(i + 1) % 3], 2 + i % 9)
                  for i in range(12)]
        _attest_pairs(client, kps, rounds)
        _wait(lambda: _settled(url, min_edges=3,
                               min_attestations=len(rounds)),
              what="leader settle")
        _wait(lambda: len(svc.store.wal.segments()) >= 2,
              what="segment rotation")
        segs0 = len(svc.store.wal.segments())
        # a catching-up consumer holds the floor: first chunk from the
        # beginning, tiny, NOT at eof
        out = svc.repl_source.wal_chunk((0, 0), max_bytes=4096,
                                        follower="slow")
        assert not out["eof"] and out["backlog"] > 0
        assert svc.repl_source.catching_up()
        svc._compact_wal(svc.tailer.persisted_cursor)
        assert len(svc.store.wal.segments()) == segs0, \
            "compaction ignored the ship floor"
        # drain the consumer to eof: the floor lifts, compaction folds
        pos = out["next"]
        while not out["eof"]:
            out = svc.repl_source.wal_chunk(pos, follower="slow")
            pos = out["next"]
        assert not svc.repl_source.catching_up()
        svc._compact_wal(svc.tailer.persisted_cursor)
        assert len(svc.store.wal.segments()) < segs0, \
            "compaction never ran after the floor lifted"
        # gap recovery end-to-end: a follower that tailed PRE-compact
        # state re-tails the folded log and converges
        stale = svc.store.wal.read_chunk((1, 8))
        assert stale["gap"] and stale["next"] == \
            svc.store.wal.earliest_position()
        fol2 = _follower(tmp_path, url, name="fstate2")
        # plant a stale cursor into a compacted-away segment
        fol2._cursor = (1, 8)
        furl2 = fol2.start()
        _wait(lambda: _follower_caught_up(furl2, url),
              what="gap-recovery catch-up")
        assert fol2.gaps >= 1
        lbody = json.loads(_get(url + "/scores")[2])
        fbody = json.loads(_get(furl2 + "/scores")[2])
        # the folded log's record order differs from the original
        # ingest order, so INTERNING order (and the list order it
        # drives) is not canonical across a gap recovery — the
        # content is: identical float per address, full vector
        assert {s["address"]: s["score"] for s in lbody["scores"]} \
            == {s["address"]: s["score"] for s in fbody["scores"]}
    finally:
        if fol2 is not None:
            fol2.shutdown()
        svc.shutdown()


# --- follower local-WAL compaction -------------------------------------------


def test_follower_local_wal_bounded_under_churn(tmp_path, devnet):
    """A long-tailing follower compacts its OWN local WAL (startup +
    snapshot cadence, fold floor = the local position at the last
    persisted replication cursor): under sustained latest-wins churn
    over a FIXED key set, the local segment count stays bounded
    instead of growing with shipped history — and a restart on the
    folded log still restores byte-equal scores."""
    _, node_url = devnet
    # leader: roomy segments, leader-side compaction OFF — the full
    # unfolded history ships, so any boundedness below is the
    # follower's own doing
    svc, client = _leader(tmp_path, node_url,
                          wal_segment_bytes=1_000_000,
                          wal_compact_segments=0,
                          snapshot_every=10_000)
    url = svc.start()
    fol2 = None
    try:
        kps = ecdsa_keypairs_from_mnemonic(MNEMONIC, 3)
        addrs = [address_from_public_key(k.public_key) for k in kps]
        fol = _follower(tmp_path, url, wal_segment_bytes=256,
                        wal_compact_segments=2, snapshot_every=3)
        furl = fol.start()
        seg_counts = []
        shipped = 0
        for r in range(8):
            # same 3 (signer, about) keys every round, round-unique
            # values (a byte-identical re-attestation would dedup
            # upstream): pure latest-wins churn — the log grows, the
            # state doesn't
            _attest_pairs(client, kps,
                          [(0, addrs[1], 10 + r),
                           (1, addrs[2], 40 + r),
                           (2, addrs[0], 70 + r)])
            shipped += 3
            _wait(lambda: _settled(url, min_edges=3,
                                   min_attestations=shipped),
                  what=f"leader settle round {r}")
            _wait(lambda: _follower_caught_up(furl, url),
                  what=f"follower catch-up round {r}")
            seg_counts.append(len(fol.store.wal.segments()))
        # 24 records at ~130 bytes against 256-byte segments is >10
        # segments unfolded; the cadence fold must keep the tail flat
        assert fol.records_applied == shipped
        assert max(seg_counts[-3:]) <= 5, seg_counts
        local_records = sum(1 for _ in fol.store.wal.replay())
        assert local_records < shipped, (local_records, shipped)
        # the folded log is still a complete restore source: SIGKILL →
        # restart on the same state dir → byte-equal scores, no gap
        _hard_kill_follower(fol)
        fol2 = _follower(tmp_path, url)
        assert fol2.follower_id == fol.follower_id
        furl2 = fol2.start()
        _wait(lambda: _follower_caught_up(furl2, url),
              what="follower catch-up after restart on folded log")
        assert fol2.gaps == 0
        lbody = json.loads(_get(url + "/scores")[2])
        fbody = json.loads(_get(furl2 + "/scores")[2])
        assert {s["address"]: s["score"] for s in lbody["scores"]} \
            == {s["address"]: s["score"] for s in fbody["scores"]}
    finally:
        if fol2 is not None:
            fol2.shutdown()
        svc.shutdown()
