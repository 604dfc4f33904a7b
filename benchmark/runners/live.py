"""Live attestation stream: the ``serve`` daemon under an open loop of
signed attestations and an open loop of score reads.

Set-up, in order:

1. the configuration's base graph (Kronecker, self-loops dropped, each
   pair kept once with its largest value), labeled by the seed; the
   signers (the peers of highest out-degree) get secp256k1 keys, every
   other peer a random address;
2. every attestation the run will land, drawn from the seed and signed
   (``benchmark/signer.py``);
3. a snapshot of the base graph written with the store's own codec
   (``store.encode_service_state``, ``snapshots.save``) into a fresh
   state directory, and a ``TrustService`` started on it: the daemon's
   restore path loads the graph;
4. warm-up through the daemon's own path: one batch of each
   power-of-two ingest bucket that the window's poll batches can reach
   (the first one revisions only, so the delta engine anchors on the
   graph as restored), then batches of new edges that take the delta
   engine's COO tail to the capacity the window needs (each capacity
   doubling is a compile of the converge); then ``stream_s`` seconds
   of the window's own traffic, its head landed at once so that no
   poll batch falls below the warmed buckets.

The window lands attestation j at ``t0 + j / attest_rate`` on the
in-memory ``LocalChain``, one per block, from this process, while a
client process (``benchmark/live_reads.py``) reads ``GET
/score/<addr>`` at ``read_rate``. Attesters are YCSB-zipfian over the
signers (rank to signer by a permutation drawn from the structure
seed, so the same peer of the structure is the hottest for every
seed); a share
``revise_share`` revises one of the attester's base edges, the rest
attest a peer drawn uniformly; values are uniform in the value range;
read addresses are zipfian over all peers.

Measured: ``freshness_p50_s``, the median over every attestation due
in the window of the time from its due time to the publish of the
first score table whose revision covers it (the publish time is taken
at the daemon's own table swap, ``ScoreRefresher._publish``; the
revision that covers an attestation at ``OpinionGraph.apply``);
``read_p50_ms``, the median of the reads' latency from their due times.
(Their 95th percentiles spread too widely from run to run to bound; the
detail line prints them.) After the close the traffic goes on at the
same rate (``tail_s`` of it is drawn, revisions only, so that it grows
no COO tail) while the run waits up to
``drain_wait_s`` for the window's last attestations to publish (late
counts in their freshness); one that never publishes, and a read that
errs, count as failed.

Compared (``check``): the daemon's final graph with the attestations as
landed and signed, up to the last one it applied (``wrong_edges``,
exact), every read with the table of the revision it reports
(``wrong_reads``, exact), the final published table with the
float64 reference over the graph of its revision (``rel_l1``), every
attestation the daemon applied with the frames of its write-ahead log,
read back once it has stopped (``unlogged``, exact: the attestation's
frame has to end at or before the log's position when its batch was
applied, in a log that fsyncs every append), and a sample of the
traffic's message hashes, drawn from the seed, with the host's scalar
Poseidon (``wrong_hashes``, exact), since on a TPU the traffic is hashed
by the program's own Poseidon kernel.
"""

from __future__ import annotations

import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import kronecker, reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READER = os.path.join(HERE, "live_reads.py")
BENCHMARK = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
MNEMONIC = ("test test test test test test test test test test test "
            "junk")
DOMAIN = bytes(20)
# the order of the secp256k1 group (SEC 2, section 2.4.1)
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
TAIL_MIN = 256     # the delta engine's first COO tail capacity
LANES_MIN = 32     # below this, recover_signers takes the host path
WAIT_POLL_S = 0.05  # the set-up's waits poll the daemon this often
WARM_WAIT_S = 900.0  # the first warm-up batch builds the plan, compiles
HASH_SAMPLE = 256   # message hashes checked on the host, about 2 ms each
SPANS = ("service.ingest", "service.graph_apply", "service.refresh",
         "service.http", "ingest.recover_batch", "ingest.pipeline",
         "service.recover_scalar", "service.operator_build")
SCOPES = ("partial", "device_partial", "sampled", "full", "rebuild")


def prepared_seconds() -> float:
    """The window the traffic is drawn for: the benchmark's
    ``run_seconds``."""
    with open(BENCHMARK) as f:
        return float(json.load(f)["run_seconds"])


def pow2_at_least(k: int) -> int:
    size = 1
    while size < k:
        size *= 2
    return size


def zipf_ranks(rng, items: int, theta: float, size: int) -> np.ndarray:
    """YCSB zipfian ranks in [0, items): P(rank r) ~ 1 / (r + 1)^theta."""
    cdf = np.cumsum(1.0 / np.arange(1, items + 1, dtype=np.float64)
                    ** theta)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size),
                                      side="right"), items - 1)


def base_graph(config: dict, seed: int):
    """``(n, src, dst, val)``: the generated graph without self-loops,
    each (src, dst) pair once with its largest generated value, in
    (src, dst) order."""
    n, src, dst, val = kronecker.graph_for(config, seed)
    keep = src != dst
    packed = np.unique((src[keep].astype(np.int64) * n + dst[keep]) * 256
                       + val[keep])
    key = packed >> 8
    last = np.append(key[1:] != key[:-1], True)
    key = key[last]
    return n, key // n, key % n, (packed[last] & 255).astype(np.float64)


def latest_wins(n, src, dst, val):
    """Each (src, dst) pair once, with its last value."""
    key = src * n + dst
    _, first_from_end = np.unique(key[::-1], return_index=True)
    last = np.sort(len(key) - 1 - first_from_end)
    return src[last], dst[last], val[last]


class Traffic:
    """Every attestation a run lands and every read it sends, drawn
    from the seed; the program is not consulted."""

    ORDER = ("warm", "pump", "stream", "window", "tail")  # landing order

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float):
        self.n, self.src, self.dst, self.val = base_graph(config, seed)
        n = self.n
        self.rate = float(traffic["attest_rate"])
        out_deg = np.bincount(self.src, minlength=n)
        self.signers = np.argsort(-out_deg, kind="stable")[
            :config["signers"]]
        # the zipfian rank -> signer map is structural: the same hub is
        # the hottest attester for every seed
        hot = kronecker.rng_for(config["structure_seed"], 2).permutation(
            len(self.signers))
        self.attesters = self.signers[hot]
        self.by_src_dst = self.dst  # base_graph sorts by src
        self.out_ptr = np.concatenate(([0], np.cumsum(out_deg)))
        self.out_deg = out_deg
        self.theta = float(traffic["attester_zipf"])
        self.revise_share = float(traffic["revise_share"])
        self.vrange = (config["value_min"], config["value_max"])
        rng = kronecker.rng_for(seed, 3)
        self.phases = {}
        poll = float(traffic["warm_poll_s"])
        lo, hi = (s * self.rate * poll for s in traffic["warm_batch_span"])
        # the stream's head lands at once, so that its first poll batch
        # is already in the warmed range
        self.stream_head = int(lo)
        self.buckets = [b for b in (1 << e for e in range(5, 17))
                        if b >= LANES_MIN and b >= pow2_at_least(int(lo))
                        and b // 2 < hi]
        warm = []
        for i, b in enumerate(self.buckets):
            warm.append(self._draw(rng, b, revise=True if i == 0 else None))
        self.phases["warm"] = warm
        m_stream = int(round(self.rate * float(traffic["stream_s"])))
        m_window = int(round(self.rate * seconds))
        self.phases["stream"] = [self._draw(rng, m_stream)]
        self.phases["window"] = [self._draw(rng, m_window)]
        # revisions only, so that the tail grows no COO tail however
        # long it runs
        self.phases["tail"] = [self._draw(
            rng, int(round(self.rate * float(traffic["tail_s"]))),
            revise=True)]
        pump_pool = self._draw(kronecker.rng_for(seed, 4), 1 << 16,
                               revise=False)
        self.base_keys = self.src * n + self.dst  # sorted
        self.phases["pump"] = self._pump(pump_pool)
        rng = kronecker.rng_for(seed, 5)
        self.read_rate = float(traffic["read_rate"])
        reads = int(round(self.read_rate * seconds))
        read_perm = rng.permutation(n)
        self.reads = read_perm[zipf_ranks(rng, n, float(
            traffic["read_zipf"]), reads)]
        rng = kronecker.rng_for(seed, 6)
        self.private_keys = [int.from_bytes(rng.bytes(32), "big") % (
            SECP_N - 1) + 1 for _ in range(len(self.signers))]
        addrs = rng.bytes(20 * n)
        self.addresses = [addrs[20 * i:20 * i + 20] for i in range(n)]
        self.nonces = [int.from_bytes(rng.bytes(32), "big") % (
            SECP_N - 1) + 1 for _ in range(self.count())]

    def _draw(self, rng, m: int, revise=None) -> tuple:
        """``(signer node ids, about node ids, values)`` of ``m``
        attestations. ``revise``: True = all revise a base edge, False
        = all attest a uniform peer, None = the traffic's share."""
        who = self.attesters[zipf_ranks(rng, len(self.attesters),
                                        self.theta, m)]
        u = rng.random(m)
        share = rng.random(m)
        other = rng.integers(0, self.n - 1, m)
        other += other >= who
        values = rng.integers(self.vrange[0], self.vrange[1] + 1, m)
        if revise is None:
            revise = share < self.revise_share
        revise = np.broadcast_to(np.asarray(revise), (m,))
        edge = self.out_ptr[who] + (u * self.out_deg[who]).astype(np.int64)
        about = np.where(revise, self.by_src_dst[np.minimum(
            edge, len(self.by_src_dst) - 1)], other)
        return who.astype(np.int64), about.astype(np.int64), \
            values.astype(np.int64)

    def _inserts(self, parts) -> int:
        """New (src, dst) pairs among ``parts``, in order, that are not
        base edges: the COO tail entries they make."""
        if not parts:
            return 0
        who = np.concatenate([p[0] for p in parts])
        about = np.concatenate([p[1] for p in parts])
        key = who * self.n + about
        base = self.base_keys
        pos = np.minimum(np.searchsorted(base, key), len(base) - 1)
        fresh = key[base[pos] != key]
        return int(len(np.unique(fresh)))

    def _pump(self, pool) -> list:
        """Batches of new edges, of the largest warmed bucket, that
        bring the delta engine's tail before the window past half the
        capacity it needs at the window's end, so that the window does
        not double it."""
        if not self.buckets:
            return []
        b = self.buckets[-1]
        later = self.phases["window"]
        batches = 0
        while True:
            p = batches * b
            pump = [tuple(a[:p] for a in pool)] if p else []
            before = self.phases["warm"][1:] + pump + self.phases["stream"]
            final = self._inserts(before + later)
            capacity = max(TAIL_MIN, pow2_at_least(final))
            if capacity == TAIL_MIN or self._inserts(before) > capacity // 2:
                return [tuple(a[k * b:(k + 1) * b] for a in pool)
                        for k in range(batches)]
            batches += 1
            if p + b > len(pool[0]):
                raise ValueError("the window's new edges outgrow the "
                                 "delta engine's tail")

    def batches(self):
        """``(phase, (signers, abouts, values))`` in landing order."""
        for phase in self.ORDER:
            for part in self.phases[phase]:
                yield phase, part

    def count(self) -> int:
        return sum(len(p[0]) for _, p in self.batches())

    def landed_graph(self, count: int | None = None):
        """Base edges and the first ``count`` attestations in landing
        order (all by default), latest wins."""
        who, about, value = (np.concatenate(a)[:count] for a in
                             zip(*(p for _, p in self.batches())))
        return latest_wins(self.n, np.concatenate([self.src, who]),
                           np.concatenate([self.dst, about]),
                           np.concatenate([self.val,
                                           value.astype(np.float64)]))


def make_chain():
    """The benchmark's chain: the program's in-memory ``LocalChain``,
    one attestation a block, where a block becomes visible to
    ``get_logs`` whole, as on a real chain, and a batch of blocks can
    land between two polls."""
    from protocol_tpu.client.chain import LocalChain

    class Chain(LocalChain):
        def __init__(self):
            super().__init__()
            self.lock = threading.Lock()

        def attest(self, creator, entries):
            with self.lock:
                return super().attest(creator, entries)

        def attest_blocks(self, rows):
            with self.lock:
                for creator, entry, _ in rows:
                    super().attest(creator, [entry])

        def get_logs(self, from_block=0):
            with self.lock:
                return super().get_logs(from_block)

    return Chain()


def span_stats(before: dict, after: dict) -> dict:
    """``{span: [count, total_s]}`` of the spans closed between two
    ``trace.summary()`` readings."""
    out = {}
    for name, b in after.items():
        a = before.get(name, {})
        count = b.get("count", 0) - a.get("count", 0)
        if count:
            out[name] = [count, b.get("total_s", 0.0) - a.get("total_s", 0.0)]
    return out


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class Run:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.et = config["eigentrust"]
        self.times: dict = {}
        self.applied: dict = {}    # signature r -> (apply time, revision)
        self.applies: list = []    # (apply time, batch size)
        self.published: list = []  # (publish time, revision, scores)
        self.logged: dict = {}     # signature r -> WAL position at apply
        self.unlogged = None
        self.service = None
        self.reader = None
        self.state = None
        self.final = None

    # --- set-up -----------------------------------------------------------
    def setup(self) -> None:
        """Everything up to the window. The window's traffic is drawn
        for the benchmark's ``run_seconds``; a shorter run lands its
        first part."""
        from benchmark import signer

        t = time.perf_counter()
        self.tr = Traffic(self.config, self.traffic, self.seed,
                          prepared_seconds())
        self.times["traffic_s"] = time.perf_counter() - t
        t = time.perf_counter()
        points = signer.base_points(self.tr.private_keys)
        signer.check_keys(self.tr.private_keys, points)
        for node, addr in zip(self.tr.signers, signer.addresses_of(points)):
            self.tr.addresses[node] = addr
        if len(set(self.tr.addresses)) != self.tr.n:
            raise ValueError("two peers drew one address")
        key_of = dict(zip(self.tr.signers.tolist(), self.tr.private_keys))
        phases, parts = zip(*self.tr.batches())
        who = np.concatenate([p[0] for p in parts]).tolist()
        about = np.concatenate([p[1] for p in parts]).tolist()
        values = np.concatenate([p[2] for p in parts]).tolist()
        atts = signer.attestations([self.tr.addresses[j] for j in about],
                                   values, DOMAIN)
        hashes = signer.attestation_hashes(atts)
        sample = kronecker.rng_for(self.seed, 7).choice(
            len(atts), min(HASH_SAMPLE, len(atts)), replace=False)
        self.wrong_hashes = sum(hashes[i] != signer.host_hash(atts[i])
                                for i in sample.tolist())
        signed = signer.signed_attestations(
            atts, hashes, [key_of[i] for i in who], self.tr.nonces)
        self.times["sign_s"] = time.perf_counter() - t
        self.landings = []  # (phase, [(creator, entry, signature r)])
        lo = 0
        for phase, part in zip(phases, parts):
            rows = []
            for k in range(lo, lo + len(part[0])):
                rows.append((self.tr.addresses[who[k]],
                             (signed[k].attestation.about,
                              signed[k].attestation.get_key(),
                              signed[k].to_payload()),
                             signed[k].signature.r))
            self.landings.append((phase, rows))
            lo += len(part[0])
        self.sequence = [row for _, rows in self.landings for row in rows]
        self.window_at = sum(len(rows) for phase, rows in self.landings
                             if phase in ("warm", "pump", "stream"))
        self.landed = self.window_at  # set-up lands everything before
        t = time.perf_counter()
        self._start_daemon()
        self.times["restore_s"] = time.perf_counter() - t
        from protocol_tpu.utils import trace

        t = time.perf_counter()
        spans = trace.summary()
        warm = [rows for phase, rows in self.landings
                if phase in ("warm", "pump")]
        for k, rows in enumerate(warm):
            # each batch lands between two polls, so the daemon ingests
            # it whole, in its bucket; the first is published before the
            # next lands (the plan build, and the delta engine anchors on
            # the restored graph), the rest once at the end
            self.chain.attest_blocks(rows)
            if not self._wait_published(WARM_WAIT_S, k == 0
                                        or k == len(warm) - 1):
                raise TimeoutError("the daemon did not take a warm-up "
                                   "batch")
        self.times["warm_s"] = time.perf_counter() - t
        self.times["warm_spans"] = span_stats(spans, trace.summary())
        t = time.perf_counter()
        stream = [rows for phase, rows in self.landings
                  if phase == "stream"][0]
        self.chain.attest_blocks(stream[:self.tr.stream_head])
        self._land_paced(stream[self.tr.stream_head:], time.perf_counter())
        self.times["stream_s"] = time.perf_counter() - t
        self.reader = subprocess.Popen(
            [sys.executable, READER], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        print(json.dumps({"setup": dict(
            self.times, peers=self.tr.n, base_edges=len(self.tr.src),
            attestations=self.tr.count(), buckets=self.tr.buckets,
            pump=sum(len(p[0]) for p in self.tr.phases["pump"]),
            host_peak_rss_bytes=rss)}), flush=True)

    def _start_daemon(self) -> None:
        from protocol_tpu.client import Client, ClientConfig
        from protocol_tpu.service import ServiceConfig, TrustService
        from protocol_tpu.service.refresh import ScoreTable
        from protocol_tpu.store import StateStore, encode_service_state

        self.state = tempfile.mkdtemp(prefix="bench-live-")
        store = StateStore(self.state)
        n = self.tr.n
        initial = np.full(n, float(self.et["initial_score"]))
        table = ScoreTable(tuple(self.tr.addresses), initial, 1, 0, 0.0,
                           True, time.time())
        arrays, meta = encode_service_state(
            self.tr.addresses, self.tr.src, self.tr.dst, self.tr.val, 1, 0,
            0, table, store.wal.position())
        store.snapshots.save(1, arrays, meta)
        store.close()
        del table, arrays
        self.chain = make_chain()
        client = Client(ClientConfig(domain="0x" + DOMAIN.hex()),
                        MNEMONIC, chain=self.chain, batched_ingest=None)
        cfg = ServiceConfig(**self.config["service"])
        self.wal_fsync = cfg.wal_fsync
        self.service = TrustService(client, cfg,
                                    os.path.join(self.state, "cursor"),
                                    provers={}, state_dir=self.state)
        self._record(self.service)
        self.url = self.service.start()

    def _record(self, service) -> None:
        """Note when each attestation is applied (at which graph
        revision, and where its write-ahead log then ended) and when
        each table is published, at the daemon's own calls."""
        graph, refresher, wal = service.graph, service.refresher, \
            service.store.wal
        apply, publish = graph.apply, refresher._publish
        table = refresher.table  # as restored
        self.published.append((time.perf_counter(), table.revision,
                               table.scores))

        def recorded_apply(batch, signers):
            logged = wal.position()
            for signed in batch:
                self.logged[signed.signature.r] = logged
            changed = apply(batch, signers)
            t, revision = time.perf_counter(), graph.revision
            for signed in batch:
                self.applied[signed.signature.r] = (t, revision)
            self.applies.append((t, len(batch)))
            return changed

        def recorded_publish(*args, **kwargs):
            table = publish(*args, **kwargs)
            self.published.append((time.perf_counter(), table.revision,
                                   table.scores))
            return table

        graph.apply = recorded_apply
        refresher._publish = recorded_publish

    def _land_paced(self, rows, t0: float) -> np.ndarray:
        """Land row j at ``t0 + j / rate``; returns each landing's lag."""
        lags = np.empty(len(rows))
        rate = self.tr.rate
        for j, (creator, entry, _) in enumerate(rows):
            due = t0 + j / rate
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.chain.attest(creator, [entry])
            lags[j] = time.perf_counter() - due
        return lags

    def _covered(self, published: bool = True) -> bool:
        """Every landed attestation applied and, with ``published``, a
        table published at the graph's revision."""
        tailer, graph = self.service.tailer, self.service.graph
        return tailer.cursor >= self.chain.block and (
            not published
            or self.service.refresher.table.revision == graph.revision)

    def _wait_published(self, limit_s: float,
                        published: bool = True) -> bool:
        deadline = time.perf_counter() + limit_s
        while not self._covered(published):
            if time.perf_counter() > deadline:
                return False
            time.sleep(WAIT_POLL_S)
        return True

    # --- the window -------------------------------------------------------
    def measure(self, window, seconds: float) -> None:
        from protocol_tpu.utils import trace

        w0 = self.window_at
        m = int(round(self.tr.rate * seconds))
        if m > len([r for phase, rows in self.landings
                    if phase == "window" for r in rows]):
            raise ValueError(f"a {seconds} s window is longer than the "
                             "traffic drawn for run_seconds")
        window_rows = self.sequence[w0:w0 + m]
        read_addresses = ["0x" + self.tr.addresses[j].hex()
                          for j in self.tr.reads]
        with window:
            t0 = window.start
            print("live: the window opens", file=sys.stderr, flush=True)
            self.spans_before = trace.summary()
            self.scopes_before = self._scopes()
            self.reader.stdin.write(json.dumps({
                "url": self.url, "t0": t0, "rate": self.tr.read_rate,
                "addresses": read_addresses}))
            self.reader.stdin.close()
            self.lags = self._land_paced(window_rows, t0)
            self.landed += m
            # the traffic goes on past the close, as it would: the last
            # window batch is polled whole, not cut at the close into a
            # bucket set-up did not warm (nor held up by the trace's end)
            self._tail_stop = threading.Event()
            self._tail = threading.Thread(
                target=self._land_tail, args=(t0 + m / self.tr.rate,),
                daemon=True)
            self._tail.start()
            rest = t0 + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
            self.spans_after = trace.summary()
            self.scopes_after = self._scopes()
        print("live: the window closes", file=sys.stderr, flush=True)
        self.window = (window.start, window.end)
        self.due = t0 + np.arange(len(window_rows)) / self.tr.rate
        self.window_r = [r for _, _, r in window_rows]
        self.drained = self._wait_window_published(
            float(self.traffic["drain_wait_s"]))
        self.reads = json.loads(self.reader.stdout.read())
        self.reader.wait()
        self.reader = None

    def _land_tail(self, t0: float) -> None:
        """Land the rows after the window at the traffic's rate until
        told to stop."""
        rate = self.tr.rate
        for j, (creator, entry, _) in enumerate(
                self.sequence[self.landed:]):
            wait = t0 + j / rate - time.perf_counter()
            if self._tail_stop.wait(max(wait, 0.0)):
                return
            self.chain.attest(creator, [entry])
            self.landed += 1

    def _wait_window_published(self, limit_s: float) -> bool:
        """Wait until every window attestation is applied and covered by
        a published table."""
        deadline = time.perf_counter() + limit_s
        while time.perf_counter() < deadline:
            got = [self.applied.get(r) for r in self.window_r]
            if all(got):
                t, rev, _ = self.published[-1]
                if rev >= max(g[1] for g in got) and t >= max(
                        g[0] for g in got):
                    return True
            time.sleep(WAIT_POLL_S)
        return False

    @staticmethod
    def _scopes() -> dict:
        from protocol_tpu.utils import trace

        return {m: trace.counter_total("refresh_sweep_scope", mode=m)
                for m in SCOPES}

    def summary(self, window) -> dict:
        pub_t = np.array([t for t, _, _ in self.published])
        pub_rev = np.array([r for _, r, _ in self.published])
        fresh, unpublished = [], 0
        for r, due in zip(self.window_r, self.due):
            got = self.applied.get(r)
            j = -1
            if got is not None:
                t_apply, revision = got
                j = max(int(np.searchsorted(pub_rev, revision)),
                        int(np.searchsorted(pub_t, t_apply)))
            if j < 0 or j >= len(pub_t):
                unpublished += 1
                continue
            fresh.append(pub_t[j] - due)
        tables = {rev: scores for _, rev, scores in self.published}
        latency, read_errors, self.wrong_reads = [], 0, 0
        for k, seconds, status, score, revision in self.reads:
            latency.append(seconds)
            if status != 200:
                read_errors += 1
                continue
            scores = tables.get(revision)
            if scores is None or float(scores[self.tr.reads[k]]) != score:
                self.wrong_reads += 1
        self.published = []
        lo, hi = self.window
        spans = {}
        for name in SPANS:
            a = self.spans_before.get(name, {})
            b = self.spans_after.get(name, {})
            spans[name] = {
                "count": b.get("count", 0) - a.get("count", 0),
                "total_s": b.get("total_s", 0.0) - a.get("total_s", 0.0)}
        scopes = {m: self.scopes_after[m] - self.scopes_before[m]
                  for m in SCOPES}
        batches = [k for t, k in self.applies if lo <= t <= hi]
        layer = {"spans": spans, "scopes": scopes,
                 "ingested": int(sum(batches)),
                 "gen_lag_ms": (1e3 * self.lags).tolist()}
        print(json.dumps({"window_detail": {
            "attestations": len(self.window_r), "reads": len(self.reads),
            "unpublished": unpublished, "read_errors": read_errors,
            "drained": self.drained, "tables": int(np.sum(
                (pub_t >= lo) & (pub_t <= hi))),
            "batches": len(batches),
            "batch_sizes": sorted(set(pow2_at_least(k) for k in batches)),
            "batch_max": max(batches, default=0),
            "batch_seq": batches,
            "span_stats": span_stats(self.spans_before, self.spans_after),
            "spans": {k: v["count"] for k, v in spans.items()},
            "scopes": scopes,
            "freshness_p95_s": percentile(fresh, 95) if fresh else None,
            # a backlog that grows shows as later quarters reading higher
            "freshness_p50_by_quarter_s": [
                percentile(q, 50) if len(q) else None
                for q in np.array_split(np.asarray(fresh), 4)],
            "read_p95_ms": 1e3 * percentile(latency, 95),
            "gen_lag_p95_ms": percentile(1e3 * self.lags, 95)}}),
            flush=True)
        return {
            "attempted": len(self.window_r) + len(self.reads),
            "failed": unpublished + read_errors,
            "end_to_end": {
                "freshness_p50_s": percentile(fresh, 50) if fresh else None,
                "read_p50_ms": 1e3 * percentile(latency, 50)},
            "layer": layer,
        }

    def release(self) -> None:
        """Drain the daemon while the traffic still lands (so that its
        tailer stops on a whole batch), keep its final graph and table,
        read its write-ahead log back, and free it."""
        self.service.shutdown(timeout=60.0)
        for thread in self.service._threads:
            thread.join()  # a poll still running past the drain's budget
        self._tail_stop.set()
        self._tail.join()
        graph, table = self.service.graph, self.service.refresher.table
        n, src, dst, val, revision, _ = graph.snapshot()
        self.final = {"n": n, "src": src, "dst": dst, "val": val,
                      "revision": revision, "addresses": graph.addresses(),
                      "invalid": graph.invalid,
                      "table": (table.revision, table.scores)}
        self.unlogged = self._unlogged(self.service.store.wal)
        self.service = self.chain = None
        shutil.rmtree(self.state, ignore_errors=True)

    def _unlogged(self, wal) -> int:
        """Applied attestations that were not durably in the log before
        their batch was applied: every one of them where the log does
        not fsync each append, else those whose frame is missing or
        ends past the log's position at their apply."""
        if self.wal_fsync != "always":
            return len(self.logged)
        r_of = {entry[2]: r for _, entry, r in self.sequence}
        frame_end = {}
        for end, (_, _, payload) in wal.replay_frames():
            r = r_of.get(payload)
            if r is not None:
                frame_end.setdefault(r, end)
        return sum(1 for r, at in self.logged.items()
                   if r not in frame_end or frame_end[r] > at)

    def check(self) -> dict:
        t = time.perf_counter()
        n = self.tr.n
        f = self.final
        revision, scores = f["table"]
        # the chain delivers in landing order, so what the daemon applied
        # is a prefix of it (its end: the last attestation it applied),
        # and its table covers the prefix applied at or below its
        # revision
        landed = [self.applied.get(r) for _, _, r in
                  self.sequence[:self.landed]]
        applied = max((i + 1 for i, a in enumerate(landed) if a), default=0)
        covered = next((i for i, a in enumerate(landed)
                        if a is None or a[1] > revision), len(landed))
        src, dst, val = self.tr.landed_graph(applied)
        wrong = edge_differences(src, dst, val, f["src"], f["dst"], f["val"])
        addrs = f["addresses"]
        wrong += max(len(addrs) - n, 0) + f["invalid"] + sum(
            a != b for a, b in zip(addrs[:n], self.tr.addresses))
        src, dst, val = self.tr.landed_graph(covered)
        ref, ref_iterations = reference.eigentrust_f64(
            n, src, dst, val, self.et["alpha"], self.et["initial_score"])
        rel = (reference.rel_l1(scores, ref) if len(scores) == n
               else 2.0)  # a table that does not score every peer
        print(json.dumps({"reference": {
            "iterations": ref_iterations, "applied": applied,
            "covered": covered, "landed": self.landed,
            "seconds": time.perf_counter() - t}}), flush=True)
        return {
            "rel_l1": {"value": rel,
                       "limit": self.config["checks"]["rel_l1"]},
            "wrong_edges": {"value": int(wrong), "limit": 0},
            "wrong_reads": {"value": int(self.wrong_reads), "limit": 0},
            "unlogged": {"value": int(self.unlogged), "limit": 0},
            "wrong_hashes": {"value": int(self.wrong_hashes), "limit": 0},
        }


def edge_differences(a_src, a_dst, a_val, b_src, b_dst, b_val) -> int:
    """Pairs in one edge list and not the other, plus pairs in both
    with different values. Each list holds a pair once."""
    m = int(max(a_src.max(initial=0), a_dst.max(initial=0),
                b_src.max(initial=0), b_dst.max(initial=0))) + 1
    ka = np.asarray(a_src, np.int64) * m + np.asarray(a_dst, np.int64)
    kb = np.asarray(b_src, np.int64) * m + np.asarray(b_dst, np.int64)
    common, ia, ib = np.intersect1d(ka, kb, assume_unique=True,
                                    return_indices=True)
    differ = np.count_nonzero(np.asarray(a_val)[ia] != np.asarray(b_val)[ib])
    return int(len(ka) + len(kb) - 2 * len(common) + differ)


def control(config: dict, seed: int) -> dict:
    """The control's reading of ``rel_l1`` at the cell's own size: the
    bfloat16 reference in the program's place, over the graph a run of
    the seed lands (``run_seconds`` of the benchmark)."""
    from benchmark import run

    bench = run.load_json(run.CHECKOUT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        traffic = run.load_json(run.HERE, "traffic",
                                cell["traffic"] + ".json")
        if cell["config"] == config["name"] and traffic["runner"] == "live":
            break
    else:
        raise ValueError(f"no live cell of {config['name']}")
    et = config["eigentrust"]
    tr = Traffic(config, traffic, seed, prepared_seconds())
    src, dst, val = tr.landed_graph()
    ref, _ = reference.eigentrust_f64(tr.n, src, dst, val, et["alpha"],
                                      et["initial_score"])
    low, iterations = reference.eigentrust_bf16(
        tr.n, src, dst, val, et["alpha"], et["initial_score"], et["tol"],
        et["max_iterations"])
    return {"rel_l1": reference.rel_l1(low, ref),
            "control_iterations": iterations}
