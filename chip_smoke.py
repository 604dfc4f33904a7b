"""Chip smoke: the served scoring path on one TPU, end to end.

    python chip_smoke.py                      # one chip, full size
    python chip_smoke.py --chips 4            # sharded converge vs one chip
    python chip_smoke.py --rehearse-on-cpu --peers 200 --attestations 400 \\
        --converge-peers 20000                # control flow, any platform

Phases, in one process (no child touches JAX — one process per chip):

1. **device** — ``jax.devices()`` must be a TPU; nothing falls back to
   the CPU.
2. **serve** — the path ``python -m protocol_tpu.cli serve`` takes, run
   in-process: a ``TrustService`` over an in-memory ``LocalChain``
   preloaded from ``--seed`` with ``--attestations`` signed attestations
   among ``--peers`` peers (signed in batches: nonce and public-key
   multiplications ride the device's fixed-base ladder). It waits until
   the published revision covers every attestation, then reads
   ``/scores``, three ``/score/<addr>``, ``/healthz`` and ``/metrics``,
   and checks that every recovered signer is the key that signed, that
   the scores match a float64 numpy power iteration, that the batched
   device ingest ran (no ``service.recover_scalar``), that the refresh
   took ``JaxRoutedBackend`` with the Pallas lane-gather kernel, and that
   no refresh failed.
3. **converge** — ``--converge-peers`` Barabási–Albert (m=8, ``--seed``)
   through ``JaxRoutedBackend.converge_edges`` on a freshly built plan:
   converged to TOL, score mass conserved, agreement with the
   float64 reference. Prints build, compile and converge seconds, the
   iterations, the device's peak bytes and the host's peak RSS.

   The default is 3,000,000 peers, not the 10,000,000 of the old
   headline: a fresh plan's host memory and time follow the padded
   edge-slot space 2^edge_e (about 1.4 slots per edge): 10.3 GB and 52 s
   at 2^26 slots in the sandbox, so 10M peers (2^28) would need about
   41 GB for the plan alone, over the one-chip host's 40 GiB. At 5M
   peers (2^27) the smoke passed on the chip with a host peak RSS of
   41.0 GB on a 45 GB host — too little margin for the gate. 3M peers is
   the largest round size at 2^26 slots.

``--chips 4`` runs only ``sharded_converge_checkpointed`` over a 4-device
mesh and the same graph's single-chip routed converge, both checked
against the float64 reference, with every device's peak bytes.

Agreement bound (relative L1 against the float64 fixed point): the f32
iteration stops when its successive relative-L1 delta is at most ``tol``;
with damping ``alpha`` the sweep is a (1 - alpha) contraction on vectors
of equal mass, so the stopped iterate lies within (1 - alpha)/alpha * tol
of the f32 fixed point, and a rounding error of at most ROUNDING_UNITS
f32 unit roundoffs per sweep moves that fixed point by at most
ROUNDING_UNITS * 2^-24 / alpha. Bound = ((1 - alpha) * tol +
ROUNDING_UNITS * 2^-24) / alpha: 2.8e-5 at TOL = 1e-6, ALPHA = 0.1.

The last line of a chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a rehearsal ends with ``{"rehearsal": "passed", ...}``. Any failure
exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
MNEMONIC = ("test test test test test test test test test test test "
            "junk")
TOL = 1e-6
ALPHA = 0.1
MAX_ITERATIONS = 500
SERVE_TIMEOUT_S = 900.0  # wait for the published scores
ROUNDING_UNITS = 32
BOUND = ((1.0 - ALPHA) * TOL + ROUNDING_UNITS * 2.0 ** -24) / ALPHA
CONSERVATION_BOUND = 1e-4  # relative; f32 mass drift over the sweeps
INITIAL_SCORE = 1000.0
SERVE_SPANS = ("service.poll", "service.wal_append", "service.ingest",
               "ingest.pipeline", "service.graph_apply", "service.refresh",
               "routed.plan_build", "converge.edges", "service.snapshot")


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str, enforce: bool = True) -> bool:
    """Record one check; an enforced failure ends the run."""
    emit({"check": what, "passed": bool(cond), "enforced": enforce})
    if enforce and not cond:
        raise SmokeFailure(what)
    return bool(cond)


def rel_l1(a, b) -> float:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.abs(a - b).sum() / np.abs(b).sum())


def reference_scores(n, src, dst, val, alpha, tol=1e-10,
                     max_iterations=10_000):
    """Float64 EigenTrust power iteration, independent of the code under
    test: duplicate edges summed, self-loops and non-positive values
    dropped, rows normalized; dangling peers hand their mass to every
    other peer; damping mixes in uniform pre-trust scaled by the total
    mass. Every peer is valid (the service and the converge phase both
    score all peers)."""
    import scipy.sparse as sp

    src = np.asarray(src)
    dst = np.asarray(dst)
    val = np.asarray(val, dtype=np.float64)
    keep = (src != dst) & (val > 0)
    a = sp.csr_matrix((val[keep], (src[keep], dst[keep])), shape=(n, n))
    a.sum_duplicates()
    row = np.asarray(a.sum(axis=1)).ravel()
    dangling = row == 0
    inv = 1.0 / np.where(dangling, 1.0, row)
    wt = (sp.diags(np.where(dangling, 0.0, inv)) @ a).T.tocsr()
    del a
    s = np.full(n, INITIAL_SCORE)
    for it in range(1, max_iterations + 1):
        corr = (s[dangling].sum() - dangling * s) / max(n - 1, 1)
        new = (1 - alpha) * (wt @ s + corr) + alpha * s.sum() / n
        delta = np.abs(new - s).sum() / np.abs(s).sum()
        s = new
        if delta <= tol:
            return s, it
    raise SmokeFailure(f"float64 reference did not reach {tol}")


def last_event(name: str) -> dict | None:
    from protocol_tpu.utils import trace

    with trace.TRACER._lock:
        events = list(trace.TRACER.events)
    for _, ev, fields in reversed(events):
        if ev == name:
            return fields
    return None


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def host_memory() -> dict:
    """This process's resident host memory now and at its peak."""
    import resource

    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                out["host_rss_bytes"] = int(line.split()[1]) * 1024  # kB
    out["host_peak_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    return out


# --- phase 1: device --------------------------------------------------------

def phase_device(args) -> dict:
    import jax
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    emit({"phase": "device", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu, **info})
    if dev.platform != "tpu" and not args.rehearse_on_cpu:
        why = f"JAX found {dev.platform}, not a TPU"
        try:
            jax.devices("tpu")
        except RuntimeError as e:
            why += f" ({e})"
        print(f"chip_smoke: no accelerator: {why}", file=sys.stderr)
        sys.exit(2)
    if args.chips and len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        sys.exit(2)
    return info


# --- phase 2: serve ---------------------------------------------------------

def make_fixture(args, on_chip: bool) -> dict:
    """Signed attestations among ``--peers`` keys, from ``--seed``.
    Unique (signer, about) pairs, no self-attestations, values 1..255.
    On the chip the public keys, message hashes and nonce points are
    batched device work; a rehearsal signs on the host with the same
    nonces (identical signatures)."""
    import random

    from protocol_tpu.client.attestation import (
        AttestationData,
        SignatureData,
        SignedAttestationData,
    )
    from protocol_tpu.crypto.secp256k1 import (
        N,
        AffinePoint,
        EcdsaKeypair,
        PublicKey,
    )

    n, m = args.peers, args.attestations
    if m > n * (n - 1):
        raise SmokeFailure(f"{m} attestations need more than {n} peers")
    rng = np.random.default_rng(args.seed)
    keys = np.empty(0, dtype=np.int64)
    while len(keys) < m:
        src = rng.integers(0, n, 2 * m)
        dst = rng.integers(0, n, 2 * m)
        cand = np.concatenate([keys, (src * n + dst)[src != dst]])
        _, first = np.unique(cand, return_index=True)
        keys = cand[np.sort(first)]
    keys = keys[:m]
    src, dst = keys // n, keys % n
    values = rng.integers(1, 256, m)
    prng = random.Random(args.seed)
    privs = [prng.randrange(1, N) for _ in range(n)]
    nonces = [prng.randrange(1, N) for _ in range(m)]
    domain = b"\x00" * 20

    t0 = time.perf_counter()
    if on_chip:
        from tools.sign_fixture import base_points_batch

        xs, ys = base_points_batch(privs)
        pubs = [PublicKey(AffinePoint(int(x), int(y)))
                for x, y in zip(xs, ys)]
    else:
        pubs = [EcdsaKeypair(d).public_key for d in privs]
    addrs = [p.to_address_bytes() for p in pubs]
    atts = [AttestationData(about=addrs[j], domain=domain, value=int(v))
            for j, v in zip(dst, values)]
    if on_chip:
        from protocol_tpu.client.ingest import _ingest_chunk, _pow2_bucket
        from protocol_tpu.models.eigentrust import HASHER_WIDTH
        from protocol_tpu.ops.poseidon_batch import get_poseidon_batch_planes
        from tools.sign_fixture import sign_batch

        rows = [[int(x) for x in (sc.about, sc.domain, sc.value, sc.message)]
                for sc in (att.to_scalar() for att in atts)]
        pb = get_poseidon_batch_planes(HASHER_WIDTH)
        cap = _ingest_chunk()
        msgs = []
        for lo in range(0, m, cap):
            part = rows[lo:lo + cap]
            pad = _pow2_bucket(len(part)) - len(part)
            msgs.extend(int(h) for h in
                        pb.hash_batch(part + [[0, 0, 0, 0]] * pad)
                        [:len(part)])
        sigs = sign_batch([privs[i] for i in src], msgs, nonces)
        # the batched signer against the host signer on a few lanes
        for i in range(min(8, m)):
            ref = EcdsaKeypair(privs[src[i]]).sign(msgs[i], nonces[i])
            if (ref.r, ref.s, ref.rec_id) != sigs[i]:
                raise SmokeFailure(f"batched signature {i} differs from "
                                   "the host signer")
    else:
        msgs = [int(att.to_scalar().hash()) for att in atts]
        sigs = []
        for i in range(m):
            sg = EcdsaKeypair(privs[src[i]]).sign(msgs[i], nonces[i])
            sigs.append((sg.r, sg.s, sg.rec_id))
    signed = [SignedAttestationData(att, SignatureData(
        r.to_bytes(32, "big"), s.to_bytes(32, "big"), rec))
        for att, (r, s, rec) in zip(atts, sigs)]
    emit({"phase": "serve", "step": "fixture", "peers": n,
          "attestations": m, "signing": "device" if on_chip else "host",
          "sign_s": time.perf_counter() - t0})
    return {"src": src, "dst": dst, "values": values, "addrs": addrs,
            "signed": signed, "domain": domain}


def _get(url: str, path: str):
    import urllib.request

    with urllib.request.urlopen(url + path, timeout=30) as r:
        body = r.read()
    return body.decode() if path == "/metrics" else json.loads(body)


def phase_serve(args, on_chip: bool) -> dict:
    import tempfile

    from protocol_tpu.client import Client, ClientConfig
    from protocol_tpu.client.attestation import DOMAIN_PREFIX
    from protocol_tpu.client.chain import LocalChain
    from protocol_tpu.ops.clos import _use_pallas
    from protocol_tpu.service import ServiceConfig, TrustService
    from protocol_tpu.utils import trace

    fx = make_fixture(args, on_chip)
    chain = LocalChain()
    key = DOMAIN_PREFIX + fx["domain"]
    order = np.argsort(fx["src"], kind="stable")
    bounds = np.flatnonzero(np.diff(fx["src"][order])) + 1
    for group in np.split(order, bounds):
        creator = fx["addrs"][fx["src"][group[0]]]
        chain.attest(creator, [(fx["signed"][i].attestation.about, key,
                                fx["signed"][i].to_payload())
                               for i in group])
    expected = {(fx["addrs"][s], fx["addrs"][d]): float(v)
                for s, d, v in zip(fx["src"], fx["dst"], fx["values"])}

    client = Client(ClientConfig(domain="0x" + fx["domain"].hex()),
                    MNEMONIC, chain=chain, batched_ingest=None)
    cfg = ServiceConfig(port=0, poll_interval=0.2, refresh_interval=0.2,
                        tol=TOL, alpha=ALPHA, pool_workers=1)
    if not on_chip:
        # a rehearsal's few hundred edges still take the routed engine
        cfg.routed_edge_threshold = 1
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as state:
        service = TrustService(client, cfg, os.path.join(state, "cursor"),
                               provers={}, state_dir=state)
        try:
            url = service.start()
            deadline = time.monotonic() + SERVE_TIMEOUT_S
            while True:
                health = _get(url, "/healthz")
                if (health["edges"] == len(expected)
                        and health["score_revision"] == health["revision"]
                        > 0):
                    break
                if last_event("service.refresh_failed") is not None:
                    raise SmokeFailure("a refresh failed while waiting")
                if time.monotonic() > deadline:
                    raise SmokeFailure(f"scores never covered every "
                                       f"attestation: {health}")
                time.sleep(0.2)
            publish_s = time.perf_counter() - t0
            table = _get(url, "/scores")
            scores = {row["address"]: row["score"]
                      for row in table["scores"]}
            picks = sorted(scores, key=scores.get)
            reads = {}
            for addr in (picks[0], picks[len(picks) // 2], picks[-1]):
                got = _get(url, f"/score/{addr}")
                reads[addr] = got["score"]
            health = _get(url, "/healthz")
            metrics = _get(url, "/metrics")
            n, src, dst, val, *_ = service.graph.snapshot()
            addresses = service.graph.addresses()[:n]
            invalid = service.graph.invalid
        finally:
            drained = service.shutdown(timeout=30.0)
    spans = trace.summary()
    compiles = trace.compile_stats()
    emit({"phase": "serve", "step": "published", "seconds": publish_s,
          "peers": health["peers"], "edges": health["edges"],
          "revision": table["revision"], "iterations": table["iterations"],
          "delta": table["delta"],
          # where the wait went: span totals on the daemon's threads
          "span_s": {name: spans[name]["total_s"] for name in SERVE_SPANS
                     if name in spans},
          "xla_compiles": compiles["compiles"],
          "xla_compile_s": compiles["compile_seconds"],
          "xla_cache_hits": compiles["cache_hits"], **host_memory()})

    got = {(addresses[s], addresses[d]): float(v)
           for s, d, v in zip(src, dst, val)}
    check(got == expected and invalid == 0,
          f"every recovered signer is the key that signed "
          f"({len(got)} edges, {invalid} invalid)")
    ref, ref_iters = reference_scores(n, src, dst, val, ALPHA)
    served = np.array([scores["0x" + a.hex()] for a in addresses])
    err = rel_l1(served, ref)
    check(err <= BOUND, f"served scores vs float64 reference: rel L1 "
                        f"{err:.3e} <= {BOUND:.3e} (reference "
                        f"{ref_iters} iterations)")
    check(all(reads[a] == scores[a] for a in reads),
          "GET /score/<addr> agrees with GET /scores")
    check("ptpu_service_refresh_total" in metrics,
          "/metrics serves the refresh counter")
    check("service.recover_scalar" not in spans
          and ("ingest.pipeline" in spans or "ingest.recover_batch" in spans),
          "signer recovery ran as batched device ingest", enforce=on_chip)
    refreshes = [r.fields.get("backend") for r in trace.TRACER.spans
                 if r.name == "service.refresh"]
    check("JaxRoutedBackend" in refreshes,
          f"refresh took JaxRoutedBackend ({refreshes})")
    # no caller passes pallas=, so routed_arrays takes this decision for
    # every routed converge in the process
    check(_use_pallas(), "routed refresh ran the Pallas lane-gather kernel",
          enforce=on_chip)
    check(last_event("service.refresh_failed") is None,
          "no service.refresh_failed event")
    check(drained, "service drained cleanly")
    return {"publish_s": publish_s}


# --- phase 3: converge ------------------------------------------------------

def ba_graph(args):
    from protocol_tpu.graph import barabasi_albert_edges

    t0 = time.perf_counter()
    src, dst, val = barabasi_albert_edges(args.converge_peers, 8,
                                          seed=args.seed)
    return src, dst, val, time.perf_counter() - t0


def routed_single(n, src, dst, val) -> dict:
    """One chip: fresh plan, a cold converge (compile carved out) and a
    warm one, through ``JaxRoutedBackend.converge_edges``."""
    from protocol_tpu.backend import JaxRoutedBackend
    from protocol_tpu.ops.clos import _use_pallas
    from protocol_tpu.ops.routed import build_routed_operator
    from protocol_tpu.utils import trace

    valid = np.ones(n, dtype=bool)
    t0 = time.perf_counter()
    op = build_routed_operator(n, src, dst, val, valid)
    plan_s = time.perf_counter() - t0
    plan_memory = host_memory()
    backend = JaxRoutedBackend()
    runs = []
    for _ in range(2):
        c0 = trace.thread_compile_seconds()
        t0 = time.perf_counter()
        scores, iters, delta = backend.converge_edges(
            n, src, dst, val, valid, INITIAL_SCORE, MAX_ITERATIONS,
            tol=TOL, alpha=ALPHA, operator=op)
        runs.append({"call_s": time.perf_counter() - t0,
                     "compile_s": trace.thread_compile_seconds() - c0,
                     "converge_s": last_event("converge.done")["seconds"]})
    return {"scores": scores, "iterations": int(iters),
            "delta": float(delta), "n_valid": int(op.n_valid),
            "plan_s": plan_s, "plan_memory": plan_memory, "runs": runs,
            "pallas": _use_pallas()}


def check_scores(label, scores, iterations, delta, n_valid, ref):
    check(delta <= TOL and iterations < MAX_ITERATIONS,
          f"{label}: converged to {delta:.3e} <= {TOL:g} in "
          f"{iterations} iterations")
    total = float(np.sum(scores, dtype=np.float64))
    want = n_valid * INITIAL_SCORE
    drift = abs(total - want) / want
    check(drift <= CONSERVATION_BOUND,
          f"{label}: score mass conserved, relative drift {drift:.3e} <= "
          f"{CONSERVATION_BOUND:g}")
    err = rel_l1(scores, ref)
    check(err <= BOUND, f"{label}: vs float64 reference rel L1 {err:.3e} "
                        f"<= {BOUND:.3e}")
    return err


def phase_converge(args, on_chip: bool) -> None:
    import jax

    n = args.converge_peers
    src, dst, val, graph_s = ba_graph(args)
    emit({"phase": "converge", "step": "graph", "peers": n,
          "edges": int(len(src)), "graph_s": graph_s, **host_memory()})
    r = routed_single(n, src, dst, val)
    emit({"phase": "converge", "step": "plan", "plan_s": r["plan_s"],
          **r["plan_memory"]})
    emit({"phase": "converge", "step": "routed",
          "compile_s": r["runs"][0]["compile_s"],
          "cold_call_s": r["runs"][0]["call_s"],
          "cold_converge_s": r["runs"][0]["converge_s"],
          "converge_s": r["runs"][1]["converge_s"],
          "warm_call_s": r["runs"][1]["call_s"],
          "iterations": r["iterations"], "delta": r["delta"],
          "pallas": r["pallas"],
          "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
          **host_memory()})
    t0 = time.perf_counter()
    ref, ref_iters = reference_scores(n, src, dst, val, ALPHA)
    emit({"phase": "converge", "step": "reference",
          "iterations": ref_iters, "seconds": time.perf_counter() - t0,
          **host_memory()})
    check_scores("converge", r["scores"], r["iterations"], r["delta"],
                 r["n_valid"], ref)
    check(r["pallas"], "converge ran the Pallas lane-gather kernel",
          enforce=on_chip)


def phase_sharded(args, on_chip: bool) -> None:
    """``sharded_converge_checkpointed`` over every device of the host
    against the same graph's single-chip routed converge. The sharded
    run goes first, so the per-device peaks it prints are its own."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from protocol_tpu.parallel import (
        build_sharded_routed_operator,
        make_mesh,
        sharded_converge_checkpointed,
    )
    from protocol_tpu.utils.checkpoint import CheckpointManager

    n = args.converge_peers
    n_dev = len(jax.devices())
    src, dst, val, graph_s = ba_graph(args)
    emit({"phase": "sharded", "step": "graph", "peers": n,
          "edges": int(len(src)), "graph_s": graph_s, **host_memory()})
    t0 = time.perf_counter()
    sop = build_sharded_routed_operator(n, src, dst, val, num_shards=n_dev)
    plan_s = time.perf_counter() - t0
    emit({"phase": "sharded", "step": "plan", "plan_s": plan_s,
          **host_memory()})
    s0 = jnp.asarray(sop.initial_scores(INITIAL_SCORE, dtype=np.float32))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ck-") as ck:
        scores, iters, delta = sharded_converge_checkpointed(
            sop, s0, make_mesh(n_dev), CheckpointManager(ck),
            tol=TOL, max_iterations=MAX_ITERATIONS, alpha=ALPHA)
        sharded = sop.scores_for_nodes(np.asarray(scores))
    emit({"phase": "sharded", "step": "sharded", "devices": n_dev,
          "call_s": time.perf_counter() - t0,
          "iterations": int(iters), "delta": float(delta),
          "peak_bytes_in_use": [peak_bytes(d) for d in jax.devices()]})
    n_valid = sop.n_valid
    del sop, s0, scores
    r = routed_single(n, src, dst, val)
    emit({"phase": "sharded", "step": "single", "plan_s": r["plan_s"],
          **r["plan_memory"],
          "converge_s": r["runs"][1]["converge_s"],
          "iterations": r["iterations"], "delta": r["delta"],
          "pallas": r["pallas"]})
    ref, _ = reference_scores(n, src, dst, val, ALPHA)
    check_scores("sharded", sharded, int(iters), float(delta), n_valid, ref)
    check_scores("single", r["scores"], r["iterations"], r["delta"],
                 r["n_valid"], ref)
    err = rel_l1(sharded, r["scores"])
    check(err <= 2 * BOUND, f"sharded vs single-chip rel L1 {err:.3e} <= "
                            f"{2 * BOUND:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--peers", type=int, default=20_000)
    ap.add_argument("--attestations", type=int, default=120_000)
    ap.add_argument("--converge-peers", type=int, default=3_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=[4],
                    help="run only the sharded converge over 4 chips and "
                         "its single-chip comparison")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run the phases on any platform; the chip-only "
                         "checks are printed, not enforced")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    device = phase_device(args)
    on_chip = device["platform"] == "tpu"
    from protocol_tpu.utils import trace
    from protocol_tpu.utils.compile_cache import enable_compile_cache

    emit({"phase": "device", "compile_cache": enable_compile_cache()})
    trace.enable()
    trace.install_compile_tracking()
    try:
        if args.chips:
            phase_sharded(args, on_chip)
        else:
            phase_serve(args, on_chip)
            phase_converge(args, on_chip)
    except Exception as e:  # noqa: BLE001 - every failure ends the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    if args.rehearse_on_cpu:
        emit({"rehearsal": "passed", "device": device})
    else:
        emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
