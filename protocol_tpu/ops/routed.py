"""Clos-routed sparse converge — the streaming SpMV for large trust graphs.

The gather-SpMV in ``ops.converge`` pays ~7 ns per edge on TPU (XLA's
general gather runs on the scalar unit). This module reformulates the
power iteration so that *no general gather appears at all*:

1. **broadcast** (streaming/MXU): edge values ``s[src]·w`` materialize in
   source-major order — block-diagonal expansion matmuls broadcast each
   node's score across its out-row lanes;
2. **route** (streaming): the edge array moves from source-major to
   destination-major order through a Clos network of lane permutations
   and transposes (``ops.clos``) — the sparse-matrix transpose as a
   permutation-network program;
3. **reduce** (streaming/MXU): lane-segmented sums collapse each
   destination row, and the per-node totals route back to state order
   through a second (node-sized) Clos network; the dangling-mass rank-1
   correction and pre-trust damping are elementwise.

Semantics are identical to ``ops.converge.spmv`` (same filtering,
normalization, redistribution — ``dynamic_sets/native.rs:234-337``).

**Memory layout rule** (the reason for the blocked representation): XLA
tiles the last two dims of every array as (8, 128); a ``[rows, 8]``
bucket array would be padded 16× in HBM — fatal at 2^28 slots. So every
large array here is either 1-D or ``[X, 128]`` with ``X ≡ 0 (mod 8)``:
a width-w < 128 bucket packs ``g = 128/w`` logical rows per lane-row,
its row-adjacent values live in lane runs, and the per-row broadcast/
reduce are contractions with constant ``[g, 128]`` / ``[128, g]``
0/1 block matrices. Row positions in the state/z vectors are
column-major in the ``[g, X]`` grid so the skinny operands of those
contractions reshape without padded copies.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .clos import (
    _apply_route_jit,
    _use_pallas,
    executor_stages,
    plan_route,
    plan_routes,
)
from .converge import (
    Semiring,
    adaptive_loop,
    dangling_and_damping,
    semiring_tail,
)
from ..graph import filter_edges, stable_argsort_bounded
from ..utils import trace

__all__ = [
    "RoutedOperator",
    "build_routed_operator",
    "ensure_edge_slots",
    "routed_arrays",
    "RoutedStatic",
    "spmv_routed",
    "converge_routed_fixed",
    "converge_routed_adaptive",
    "spmv_routed_semiring",
    "converge_routed_fixed_semiring",
    "converge_routed_adaptive_semiring",
    "converge_routed_topics",
]


def _ceil_pow2_exp(x: int, floor: int = 7) -> int:
    e = floor
    while (1 << e) < x:
        e += 1
    return e


def _initial_scores(valid: np.ndarray, initial: float, dtype) -> np.ndarray:
    return (valid * initial).astype(dtype)


def _scores_for_nodes(state_to_node: np.ndarray, n: int,
                      state_scores) -> np.ndarray:
    state_scores = np.asarray(state_scores)
    out = np.zeros(n, dtype=state_scores.dtype)
    live = state_to_node >= 0
    out[state_to_node[live]] = state_scores[live]
    return out


def _scores_from_nodes(state_to_node: np.ndarray, valid: np.ndarray,
                       node_scores, dtype) -> np.ndarray:
    """Inverse of ``_scores_for_nodes``: scatter a node-order vector into
    state-slot order (dead slots stay 0) — the warm-start seam for the
    routed engines (a previous converge's node scores restart the next)."""
    node_scores = np.asarray(node_scores, dtype=np.float64)
    out = np.zeros(len(state_to_node), dtype=np.float64)
    live = state_to_node >= 0
    out[live] = node_scores[state_to_node[live]]
    return (out * valid).astype(dtype)


def blocked_broadcast(arrs: dict, s, widths: tuple, xs: tuple,
                      total_len: int):
    """Expand a state(-slice) vector into weighted edge values across the
    blocked buckets: the shared source side of the routed SpMV (used by
    the single-device and the per-shard kernels)."""
    parts = []
    pos = 0
    for bi, (w, X) in enumerate(zip(widths, xs)):
        w_mat = arrs["out_weight"][bi]
        if w < 128:
            g = 128 // w
            s2t = lax.slice_in_dim(s, pos, pos + g * X).reshape(g, X)
            v = jnp.einsum("gl,gx->xl", arrs["out_expand"][bi], s2t,
                           precision=_PREC) * w_mat
            pos += g * X
        else:
            nb_pad = X * 128 // w        # padded row count
            rows = lax.slice_in_dim(s, pos, pos + nb_pad)
            v = jnp.broadcast_to(
                rows[:, None], (nb_pad, w // 128)).reshape(X, 1) * w_mat
            pos += nb_pad
        parts.append(v.reshape(-1))
    used = sum(X * 128 for X in xs)
    parts.append(jnp.zeros((total_len - used,), dtype=s.dtype))
    return jnp.concatenate(parts)


def blocked_reduce(arrs: dict, y, widths: tuple, xs: tuple, n_pos: int,
                   total_len: int):
    """Lane-segmented per-row sums of a routed edge array: the shared
    destination side of the routed SpMV."""
    sums = []
    off = 0
    for bi, (w, X) in enumerate(zip(widths, xs)):
        y2 = lax.slice_in_dim(y, off, off + X * 128).reshape(X, 128)
        if w < 128:
            z2 = jnp.einsum("xl,gl->gx", y2, arrs["in_reduce"][bi],
                            precision=_PREC)
            sums.append(z2.reshape(-1))
        else:
            nb_pad = X * 128 // w
            sums.append(y2.sum(axis=-1).reshape(nb_pad, w // 128).sum(axis=-1))
        off += X * 128
    sums.append(jnp.zeros((total_len - n_pos,), dtype=y.dtype))
    return jnp.concatenate(sums)


class _Side(NamedTuple):
    """One blocked ELL side (source or destination).

    widths[b]: logical row width (pow2). xs[b]: physical lane-rows,
    multiple of 8. weight[b]: [X, 128] float64. slot_base[b]: first flat
    slot. pos_base[b]: first row-position in the side's position space
    (state order for the source side, z order for the destination side).
    row_nodes[b]: node id per logical row (length ≤ g·X; pad rows absent).
    row_pos[b]: position of each logical row — column-major in the
    [g, X] grid. edge_slot: flat slot per input edge. n_slots / n_pos:
    totals (pads included).
    """

    widths: tuple
    xs: tuple
    weight: list
    slot_base: tuple
    pos_base: tuple
    row_nodes: list
    row_pos: list
    edge_slot: np.ndarray
    n_slots: int
    n_pos: int


def _bucketize_blocked(n, key, other, weight, min_width=8):
    """Group edges by ``key`` node into blocked pow2-width ELL buckets."""
    order = stable_argsort_bounded(key, n)
    key_s = key[order].astype(np.int64)
    w_s = weight[order]

    deg = np.bincount(key_s, minlength=n).astype(np.int64)
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=ptr[1:])
    offset_in_row = np.arange(len(key_s), dtype=np.int64) - ptr[key_s]

    widths_per_row = np.maximum(
        min_width, 2 ** np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
    )
    widths_per_row[deg == 0] = 0
    used = tuple(sorted(int(w) for w in np.unique(widths_per_row) if w > 0))

    widths, xs, wmats, slot_bases, pos_bases = [], [], [], [], []
    row_nodes_l, row_pos_l = [], []
    edge_slot = np.empty(len(key_s), dtype=np.int64)
    slot_base = 0
    pos_base = 0
    for w in used:
        rows = np.nonzero(widths_per_row == w)[0]
        nb = len(rows)
        if w < 128:
            g = 128 // w                 # logical rows per lane-row
            X = -(-nb // g)              # lane-rows…
            X = -(-X // 8) * 8           # …padded to a multiple of 8
            n_pos_b = g * X              # padded grid positions
        else:
            X = nb * (w // 128)
            X = -(-X // 8) * 8
            # X stays divisible by w/128 (either w/128 ≤ 8 and X is a
            # multiple of 8, or nb·w/128 is already a multiple of 8)
            n_pos_b = X * 128 // w       # padded row count

        local = np.full(n, -1, dtype=np.int64)
        local[rows] = np.arange(nb)
        mask = widths_per_row[key_s] == w
        r = local[key_s[mask]]
        off = offset_in_row[mask]
        if w < 128:
            slot = (r // g) * 128 + (r % g) * w + off
            rpos = (np.arange(nb) % g) * X + np.arange(nb) // g
        else:
            slot = r * w + off           # [X, 128] row-major view
            rpos = np.arange(nb)

        wm = np.zeros(X * 128, dtype=np.float64)
        wm[slot] = w_s[mask]
        wmats.append(wm.reshape(X, 128))
        edge_slot[mask] = slot_base + slot

        widths.append(w)
        xs.append(X)
        slot_bases.append(slot_base)
        pos_bases.append(pos_base)
        row_nodes_l.append(rows)
        row_pos_l.append(pos_base + rpos)
        slot_base += X * 128
        pos_base += n_pos_b

    # undo the sort for edge_slot
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    return _Side(
        widths=tuple(widths),
        xs=tuple(xs),
        weight=wmats,
        slot_base=tuple(slot_bases),
        pos_base=tuple(pos_bases),
        row_nodes=row_nodes_l,
        row_pos=row_pos_l,
        edge_slot=edge_slot[inv],
        n_slots=slot_base,
        n_pos=pos_base,
    )


def save_operator_npz(op, path) -> None:
    """Field-driven npz serialization shared by the routed operators.

    Every dataclass field is stored under a named, type-tagged key
    (``int_*`` scalar, ``tup_*`` int tuple, ``arr_*`` array,
    ``lst_*_{i}`` list of arrays) — no positional meta vector to
    mis-index. The write is atomic (tmp + rename) so an interrupted run
    can never leave a truncated file under the final name."""
    import dataclasses
    import os

    payload = {"fmt_version": np.asarray(2, dtype=np.int64)}
    for f in dataclasses.fields(op):
        v = getattr(op, f.name)
        if v is None:
            continue  # optional field left unset: loaders default it
        if isinstance(v, (int, np.integer)):
            payload[f"int_{f.name}"] = np.asarray(v, dtype=np.int64)
        elif isinstance(v, tuple):
            payload[f"tup_{f.name}"] = np.asarray(v, dtype=np.int64)
        elif isinstance(v, np.ndarray):
            payload[f"arr_{f.name}"] = v
        elif isinstance(v, list):
            payload[f"cnt_{f.name}"] = np.asarray(len(v), dtype=np.int64)
            for i, a in enumerate(v):
                payload[f"lst_{f.name}_{i}"] = np.asarray(a)
        else:  # pragma: no cover - new field types need a tag here
            raise TypeError(f"unserializable field {f.name}: {type(v)}")
    path = str(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as fh:  # file object: savez cannot append
            np.savez(fh, **payload)  # its own .npz suffix to the tmp name
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def save_operator_dir(op, path) -> None:
    """Directory twin of :func:`save_operator_npz`: one raw ``.npy``
    per array plus a ``meta.json``. No zip container means no CRC32
    pass and no chunked copies on load — at 10M peers (4 GB) the load
    drops from ~11 s (npz) to disk-stream speed (~3.5 s). Atomic via
    tmp-dir + rename."""
    import dataclasses
    import json
    import os
    import shutil

    path = str(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(tmp, exist_ok=True)
        meta = {"fmt_version": 3, "ints": {}, "tups": {}, "arrays": [],
                "lists": {}}
        for f in dataclasses.fields(op):
            v = getattr(op, f.name)
            if v is None:
                continue  # optional field left unset: loaders default it
            if isinstance(v, (int, np.integer)):
                meta["ints"][f.name] = int(v)
            elif isinstance(v, tuple):
                meta["tups"][f.name] = [int(x) for x in v]
            elif isinstance(v, np.ndarray):
                np.save(os.path.join(tmp, f"arr_{f.name}.npy"), v)
                meta["arrays"].append(f.name)
            elif isinstance(v, list):
                meta["lists"][f.name] = len(v)
                for i, a in enumerate(v):
                    np.save(os.path.join(tmp, f"lst_{f.name}_{i}.npy"),
                            np.asarray(a))
            else:  # pragma: no cover - new field types need a tag here
                raise TypeError(
                    f"unserializable field {f.name}: {type(v)}")
        with open(os.path.join(tmp, "meta.json"), "w") as fh:
            json.dump(meta, fh)
        # swap the old cache out from under the final name, then swap
        # the new one in; if the final rename loses a race, restore the
        # old cache rather than leaking it
        old = f"{path}.old.{os.getpid()}"
        if os.path.isdir(path):
            os.rename(path, old)
        elif os.path.exists(path):
            os.unlink(path)
            old = None
        else:
            old = None
        try:
            os.rename(tmp, path)
        except OSError:
            if old is not None:
                if not os.path.exists(path):
                    try:
                        os.rename(old, path)  # previous cache back
                    except OSError:
                        pass  # surface the original failure below
                else:  # a concurrent writer won the race — drop ours
                    shutil.rmtree(old, ignore_errors=True)
            raise
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def load_operator_dir(cls, path, mmap: bool = True):
    """Inverse of :func:`save_operator_dir`.

    ``mmap=True`` (default) memory-maps every array: the operator is
    usable immediately and its ~4 GB (at 10M peers) page in exactly
    once, on demand, during device staging — instead of a full eager
    read (disk-bound, ~19 s cold) followed by a second pass in
    device_put. The maps are read-only; consumers that mutate must
    copy (none do)."""
    import dataclasses
    import json
    import os

    mode = "r" if mmap else None
    with open(os.path.join(path, "meta.json")) as fh:
        meta = json.load(fh)
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name in meta["ints"]:
            kwargs[f.name] = meta["ints"][f.name]
        elif f.name in meta["tups"]:
            kwargs[f.name] = tuple(meta["tups"][f.name])
        elif f.name in meta["arrays"]:
            kwargs[f.name] = np.load(
                os.path.join(path, f"arr_{f.name}.npy"), mmap_mode=mode)
        elif f.name in meta["lists"]:
            kwargs[f.name] = [
                np.load(os.path.join(path, f"lst_{f.name}_{i}.npy"),
                        mmap_mode=mode)
                for i in range(meta["lists"][f.name])
            ]
        elif f.default is not dataclasses.MISSING:
            kwargs[f.name] = f.default  # optional field, older cache
        else:
            raise ValueError(f"operator dir is missing field {f.name}")
    return cls(**kwargs)


def load_operator_npz(cls, z):
    """Inverse of :func:`save_operator_npz` for an open npz handle."""
    import dataclasses

    kwargs = {}
    for f in dataclasses.fields(cls):
        if f"int_{f.name}" in z:
            kwargs[f.name] = int(z[f"int_{f.name}"])
        elif f"tup_{f.name}" in z:
            kwargs[f.name] = tuple(int(x) for x in z[f"tup_{f.name}"])
        elif f"arr_{f.name}" in z:
            kwargs[f.name] = z[f"arr_{f.name}"]
        elif f"cnt_{f.name}" in z:
            kwargs[f.name] = [z[f"lst_{f.name}_{i}"]
                              for i in range(int(z[f"cnt_{f.name}"]))]
        elif f.default is not dataclasses.MISSING:
            kwargs[f.name] = f.default  # optional field, older cache
        else:
            raise ValueError(f"operator file is missing field {f.name}")
    return cls(**kwargs)


@dataclass
class RoutedOperator:
    """Host-side routed operator: blocked layouts, masks, route plans."""

    n: int
    n_valid: int
    nnz: int
    out_widths: tuple
    out_xs: tuple
    out_weight: list       # per bucket [X, 128] float64
    n_src_pos: int         # state slots occupied by source rows (pads incl.)
    state_to_node: np.ndarray  # state slot -> node id, -1 for dead slots
    in_widths: tuple
    in_xs: tuple
    in_n_pos: int
    edge_e: int
    edge_bits: tuple
    edge_stages: list
    state_e: int
    state_bits: tuple
    state_stages: list
    valid: np.ndarray      # [2^state_e] f32
    dangling: np.ndarray
    # flat out-side slot per FILTERED edge (the order filter_edges
    # returns — sorted by src*n+dst). The seam the incremental delta
    # engine patches through: slot -> (bucket, lane-row, lane) addresses
    # one value in the out_weight buffers. None on operators built (or
    # cached) before the delta engine existed; ensure_edge_slots
    # upgrades those in O(E) without a plan rebuild.
    out_edge_slot: np.ndarray | None = None
    # the bucket-width floor the build ran with — persisted because the
    # slot math is a function of it: ensure_edge_slots re-deriving
    # slots under a different min_width would scatter patches into the
    # wrong (row, lane) positions. Caches from before this field
    # load as 8 (the only default any cached operator was built with).
    min_width: int = 8

    def __post_init__(self):
        # routed_arrays' resident staging, keyed (dtype, alpha, pallas):
        # not a field, so save() never writes it and the device arrays
        # are freed with the operator
        self._staged: dict = {}

    @property
    def n_state(self) -> int:
        return 1 << self.state_e

    def initial_scores(self, initial: float, dtype=np.float32) -> np.ndarray:
        return _initial_scores(self.valid, initial, dtype)

    def scores_for_nodes(self, state_scores: np.ndarray) -> np.ndarray:
        """Translate a state-order score vector to node order."""
        return _scores_for_nodes(self.state_to_node, self.n, state_scores)

    def scores_from_nodes(self, node_scores: np.ndarray,
                          dtype=np.float32) -> np.ndarray:
        """Translate a node-order score vector to state order (warm start)."""
        return _scores_from_nodes(self.state_to_node, self.valid,
                                  node_scores, dtype)

    def save(self, path) -> None:
        """Persist the compiled operator so the one-time routing-plan
        compilation is reusable across runs. A path WITHOUT an ``.npz``
        suffix uses the raw-directory format (3× faster loads at 10M);
        ``.npz`` keeps the legacy container. Weights stay float64: the
        f64 converge path must round-trip losslessly."""
        if str(path).endswith(".npz"):
            save_operator_npz(self, path)
        else:
            save_operator_dir(self, path)

    @classmethod
    def load(cls, path) -> "RoutedOperator":
        import os

        if os.path.isdir(path):
            return load_operator_dir(cls, path)
        with np.load(path) as z:
            if "fmt_version" in z:
                return load_operator_npz(cls, z)
            # legacy v1 format (positional meta vector), kept readable so
            # pre-existing operator caches stay valid
            meta = z["meta"]
            out_widths = tuple(int(w) for w in z["out_widths"])
            return cls(
                n=int(meta[0]),
                n_valid=int(meta[1]),
                nnz=int(meta[2]),
                out_widths=out_widths,
                out_xs=tuple(int(x) for x in z["out_xs"]),
                out_weight=[z[f"out_weight_{i}"]
                            for i in range(len(out_widths))],
                n_src_pos=int(meta[3]),
                state_to_node=z["state_to_node"],
                in_widths=tuple(int(w) for w in z["in_widths"]),
                in_xs=tuple(int(x) for x in z["in_xs"]),
                in_n_pos=int(meta[6]),
                edge_e=int(meta[4]),
                edge_bits=tuple(int(b) for b in z["edge_bits"]),
                edge_stages=list(z["edge_stages"]),
                state_e=int(meta[5]),
                state_bits=tuple(int(b) for b in z["state_bits"]),
                state_stages=list(z["state_stages"]),
                valid=z["valid"],
                dangling=z["dangling"],
            )


def build_routed_operator(
    n, src, dst, val, valid=None, min_width: int = 8,
    prefer_native: bool = True,
) -> RoutedOperator:
    """Filter + normalize an edge list and compile the routing program.

    Semantics of ``filter_edges`` (the reference's opinion filter,
    ``dynamic_sets/native.rs:234-283``) are shared with the gather path.

    The build is the converge path's one-time compilation cost (minutes
    at 10M peers) — spanned and recorded as
    ``ptpu_routed_plan_build_seconds`` so operator-cache misses are
    attributable in the serve daemon's refresh latency.
    """
    # every full routing-plan compilation anywhere in the process —
    # the write-path cost the delta engine exists to amortize away; the
    # serve smoke asserts this stays FLAT under weight-revision churn
    trace.counter("operator_full_builds").inc()
    with trace.timed("routed_plan_build_seconds", "routed.plan_build",
                     n=n, edges=len(src)):
        op = _build_routed_operator(n, src, dst, val, valid, min_width,
                                    prefer_native)
    return op


def ensure_edge_slots(op: RoutedOperator, src, dst, weight) \
        -> RoutedOperator:
    """Upgrade a pre-delta-engine operator (cached without
    ``out_edge_slot``) in place: recompute the out-side bucketization —
    O(E) numpy, NO routing-plan rebuild — for the same filtered edge
    arrays the operator was built from. Deterministic: the slot math is
    the exact ``_bucketize_blocked`` pass the build ran, under the
    ``min_width`` the operator persists."""
    if op.out_edge_slot is None:
        op.out_edge_slot = _bucketize_blocked(
            n=op.n, key=np.asarray(src), other=np.asarray(dst),
            weight=np.asarray(weight), min_width=op.min_width).edge_slot
    return op


def _build_routed_operator(
    n, src, dst, val, valid, min_width: int, prefer_native: bool,
) -> RoutedOperator:
    # child spans of routed.plan_build, one per phase: filter,
    # bucketize, assemble, route, finish
    with trace.span("routed.plan.filter"):
        src, dst, weight, valid_mask, dangling = filter_edges(
            n, src, dst, val, valid)

    # the two sides bucketize independently — overlap them on threads
    # (numpy's big sorts release the GIL), like the two plan builds
    # below; PTPU_PLAN_SERIAL=1 restores single-thread scheduling
    import os as _os
    from concurrent.futures import ThreadPoolExecutor

    with trace.span("routed.plan.bucketize"):
        if _os.environ.get("PTPU_PLAN_SERIAL", "0") != "1":
            with ThreadPoolExecutor(max_workers=2) as pool:
                out_f = pool.submit(_bucketize_blocked, n, src, dst, weight,
                                    min_width)
                in_f = pool.submit(_bucketize_blocked, n, dst, src, weight,
                                   min_width)
                out_side, in_side = out_f.result(), in_f.result()
        else:
            out_side = _bucketize_blocked(n, src, dst, weight, min_width)
            in_side = _bucketize_blocked(n, dst, src, weight, min_width)
    with trace.span("routed.plan.assemble"):
        perm, sperm, state_e, state_to_node, live = _assemble_permutations(
            n, out_side, in_side)
    # both plans at once: the state plan (2^state_e, typically 16x
    # smaller) rides in the edge plan's shadow — the threaded plan
    # build is the DEFAULT full-rebuild fast path
    with trace.span("routed.plan.route"):
        plan, splan = plan_routes((perm, sperm), prefer_native=prefer_native)

    with trace.span("routed.plan.finish"):
        N2 = 1 << state_e
        live_slots = np.nonzero(live)[0]
        live_nodes = state_to_node[live]
        valid_state = np.zeros(N2, dtype=np.float32)
        valid_state[live_slots] = valid_mask[live_nodes].astype(np.float32)
        dangling_state = np.zeros(N2, dtype=np.float32)
        dangling_state[live_slots] = dangling[live_nodes].astype(np.float32)

        return RoutedOperator(
            n=n,
            n_valid=int(valid_mask.sum()),
            nnz=len(src),
            out_widths=out_side.widths,
            out_xs=out_side.xs,
            out_weight=out_side.weight,
            n_src_pos=out_side.n_pos,
            state_to_node=state_to_node,
            in_widths=in_side.widths,
            in_xs=in_side.xs,
            in_n_pos=in_side.n_pos,
            edge_e=plan.e,
            edge_bits=plan.bits,
            edge_stages=plan.stages,
            state_e=splan.e,
            state_bits=splan.bits,
            state_stages=splan.stages,
            valid=valid_state,
            dangling=dangling_state,
            out_edge_slot=out_side.edge_slot,
            min_width=min_width,
        )


def _assemble_permutations(n, out_side: _Side, in_side: _Side):
    """The state order and the two permutations the plan routes:
    ``perm`` (edge route, in slot <- out slot) and ``sperm`` (state
    route, state slot <- z position). Returns ``(perm, sperm, state_e,
    state_to_node, live)``."""
    # state order: source-row positions first (column-major grids, dead
    # pad slots included), then out-edge-less nodes
    n_src_pos = out_side.n_pos
    src_pos = (np.concatenate(out_side.row_pos) if out_side.row_pos
               else np.zeros(0, dtype=np.int64))
    src_nodes = (np.concatenate(out_side.row_nodes) if out_side.row_nodes
                 else np.zeros(0, dtype=np.int64))
    has_out = np.zeros(n, dtype=bool)
    has_out[src_nodes] = True
    rest = np.nonzero(~has_out)[0]

    state_e = _ceil_pow2_exp(max(n_src_pos + len(rest), in_side.n_pos, 128))
    N2 = 1 << state_e
    state_to_node = np.full(N2, -1, dtype=np.int64)
    state_to_node[src_pos] = src_nodes
    state_to_node[n_src_pos : n_src_pos + len(rest)] = rest
    live = state_to_node >= 0

    # --- edge route: in slot <- out slot ---------------------------------
    # int32 throughout: these are 2^28-sized working arrays at 10M-peer
    # scale — int64 doubles their alloc + scatter traffic for slot ids
    # that fit 31 bits by construction (edge_e ≤ 31)
    edge_e = _ceil_pow2_exp(max(out_side.n_slots, in_side.n_slots, 128))
    E2 = 1 << edge_e
    assert edge_e <= 31, "edge slot space exceeds int32 (scale the " \
        "assembly dtypes before routing this graph)"
    perm = np.full(E2, -1, dtype=np.int32)
    perm[in_side.edge_slot] = out_side.edge_slot
    src_used = np.zeros(E2, dtype=bool)
    src_used[out_side.edge_slot] = True
    free_src = np.nonzero(~src_used)[0]   # out-ELL pads + tail: all zeros
    need = np.nonzero(perm < 0)[0]        # in-ELL pads + tail
    perm[need] = free_src[: len(need)]

    # --- state route: state slot <- z position ---------------------------
    # z = concatenated per-bucket in-row sums (column-major positions)
    in_nodes = (np.concatenate(in_side.row_nodes) if in_side.row_nodes
                else np.zeros(0, dtype=np.int64))
    in_pos = (np.concatenate(in_side.row_pos) if in_side.row_pos
              else np.zeros(0, dtype=np.int64))
    node_in_pos = np.full(n, -1, dtype=np.int64)
    node_in_pos[in_nodes] = in_pos
    assert state_e <= 31, "state slot space exceeds int32 (scale the " \
        "assembly dtypes before routing this graph)"
    sperm = np.full(N2, -1, dtype=np.int32)
    live_nodes = state_to_node[live]
    live_slots = np.nonzero(live)[0]
    with_in = node_in_pos[live_nodes] >= 0
    sperm[live_slots[with_in]] = node_in_pos[live_nodes[with_in]]
    sp_used = np.zeros(N2, dtype=bool)
    sp_used[sperm[sperm >= 0]] = True
    free_zero = np.nonzero(~sp_used)[0]   # z pads + tail: all zeros
    need = np.nonzero(sperm < 0)[0]
    sperm[need] = free_zero[: len(need)]
    return perm, sperm, state_e, state_to_node, live


class RoutedStatic(NamedTuple):
    """Hashable static config for the jitted routed kernels."""

    out_widths: tuple
    out_xs: tuple
    in_widths: tuple
    in_xs: tuple
    in_n_pos: int
    edge_e: int
    edge_bits: tuple
    state_e: int
    state_bits: tuple
    pallas: bool


def _expand_matrix(w: int, dtype) -> np.ndarray:
    """B[g, 128]: lane l takes grid row l // w."""
    g = 128 // w
    lanes = np.arange(128)
    return (lanes // w == np.arange(g)[:, None]).astype(dtype)


def routed_arrays(op: RoutedOperator, dtype=jnp.float32, alpha: float = 0.0,
                  pretrust=None, pallas: bool | None = None):
    """Device pytree + static config. ``alpha`` as in
    ``ops.converge.operator_arrays``. ``pretrust``, unlike the gather
    path's node-order vector, must be in **state order** with length
    ``2^state_e`` (zero on dead slots) — translate a node-order vector u
    via ``u[op.state_to_node] * (op.state_to_node >= 0)`` padded to
    ``op.n_state``; the default is uniform over valid peers.

    With the default pre-trust the arrays stay resident on ``op``: it is
    staged once per ``(dtype, alpha, pallas)``, and every later call
    uploads nothing. An explicit ``pretrust`` is staged afresh each call
    and not kept. Each call returns a new dict over the shared arrays,
    so a caller may replace its entries (the delta engine patches its
    own) without reaching ``op`` or another caller; nothing may donate
    them. The ``routed_stage`` counter counts each call as a ``hit`` or
    a ``miss``."""
    pallas = _use_pallas() if pallas is None else bool(pallas)
    key = (np.dtype(dtype), float(alpha), pallas)
    staged = op._staged.get(key) if pretrust is None else None
    trace.counter("routed_stage").inc(
        result="miss" if staged is None else "hit")
    if staged is None:
        staged = _stage_routed(op, dtype, alpha, pretrust, pallas)
        if pretrust is None:
            op._staged[key] = staged
    arrs, static = staged
    return dict(arrs), static


def _stage_routed(op: RoutedOperator, dtype, alpha: float, pretrust,
                  pallas: bool):
    """Upload ``op``'s arrays for :func:`routed_arrays`."""
    if pretrust is None:
        pretrust = op.valid.astype(np.float64) / max(op.n_valid, 1)
    arrs = {
        "out_weight": tuple(jnp.asarray(w, dtype=dtype) for w in op.out_weight),
        "out_expand": tuple(
            jnp.asarray(_expand_matrix(w, np.float32), dtype=dtype)
            if w < 128 else None
            for w in op.out_widths),
        "in_reduce": tuple(
            jnp.asarray(_expand_matrix(w, np.float32), dtype=dtype)
            if w < 128 else None
            for w in op.in_widths),
        "edge_stages": executor_stages(
            (jnp.asarray(s) for s in op.edge_stages), op.edge_bits, "edge"),
        "state_stages": executor_stages(
            (jnp.asarray(s) for s in op.state_stages), op.state_bits,
            "state"),
        "valid": jnp.asarray(op.valid, dtype=dtype),
        "dangling": jnp.asarray(op.dangling, dtype=dtype),
        "n_valid": jnp.asarray(float(op.n_valid), dtype=dtype),
        "alpha": jnp.asarray(float(alpha), dtype=dtype),
        "pretrust": jnp.asarray(pretrust, dtype=dtype),
    }
    static = RoutedStatic(
        out_widths=op.out_widths,
        out_xs=op.out_xs,
        in_widths=op.in_widths,
        in_xs=op.in_xs,
        in_n_pos=op.in_n_pos,
        edge_e=op.edge_e,
        edge_bits=op.edge_bits,
        state_e=op.state_e,
        state_bits=op.state_bits,
        pallas=pallas,
    )
    return arrs, static


_PREC = lax.Precision.HIGHEST


def spmv_routed(arrs: dict, static: RoutedStatic, s: jnp.ndarray) -> jnp.ndarray:
    """One application of the normalized trust operator (state order):
    broadcast → route → reduce → route-back → dangling + damping.

    Two optional keys turn this into the delta engine's PATCHED matvec
    (both branches are trace-time — present/absent splits the jit
    cache, never recompiles within a mode):

    - ``inv_row_scale`` ([2^state_e]): per-source-row normalization
      correction. The weight buffers store ``val / row_sum_at_build``;
      after in-place value patches the true row sum drifts, and scaling
      the *source score* by ``row_sum_at_build / row_sum_now`` restores
      exact normalization without rescattering O(out-degree) slots per
      revision.
    - ``tail_src``/``tail_dst``/``tail_w`` (fixed-capacity COO, state
      order): structural inserts applied since the last plan build —
      folded in with one scatter-add; unused capacity carries weight 0.
      The routing program itself never changes, so edge churn costs
      O(batch), not O(graph).

    Each phase runs under a ``jax.named_scope`` (``broadcast``,
    ``edge_route``, ``reduce``, ``state_route``, ``tail``): the names
    land in the device operations' metadata, so a profiler trace sums
    device time per phase whatever XLA numbers its instructions.
    """
    with jax.named_scope("broadcast"):
        s_b = s * arrs["inv_row_scale"] if "inv_row_scale" in arrs else s
        x = blocked_broadcast(arrs, s_b, static.out_widths, static.out_xs,
                              1 << static.edge_e)
    with jax.named_scope("edge_route"):
        y = _apply_route_jit(x, arrs["edge_stages"], static.edge_e,
                             static.edge_bits, static.pallas)
    with jax.named_scope("reduce"):
        z = blocked_reduce(arrs, y, static.in_widths, static.in_xs,
                           static.in_n_pos, 1 << static.state_e)
    with jax.named_scope("state_route"):
        base = _apply_route_jit(z, arrs["state_stages"], static.state_e,
                                static.state_bits, static.pallas)
    with jax.named_scope("tail"):
        if "tail_w" in arrs:
            # tail weights are TRUE normalized weights (val /
            # row_sum_now, maintained host-side per batch) — no
            # inv_row_scale here
            base = base + jnp.zeros_like(base).at[arrs["tail_dst"]].add(
                arrs["tail_w"] * s[arrs["tail_src"]])
        return dangling_and_damping(arrs, s, base)


@partial(jax.jit, static_argnames=("static", "num_iterations"))
def converge_routed_fixed(arrs, static: RoutedStatic, s0, num_iterations: int):
    """Reference-parity fixed-iteration power iteration, routed."""
    return lax.fori_loop(
        0, num_iterations, lambda _, s: spmv_routed(arrs, static, s), s0
    )


@partial(jax.jit, static_argnames=("static", "max_iterations", "accel_every"))
def converge_routed_adaptive(arrs, static: RoutedStatic, s0,
                             tol: float = 1e-6, max_iterations: int = 100,
                             accel_every: int = 0):
    """Iterate until the relative L1 delta ≤ tol (or max_iterations).
    ``accel_every`` enables the safeguarded extrapolation (see
    ``ops.converge.adaptive_loop``). Returns (scores, iterations_run,
    final_relative_delta)."""
    return adaptive_loop(
        lambda s: spmv_routed(arrs, static, s), s0, tol, max_iterations,
        accel_every,
    )


# --- generalized-semiring routed sweep -------------------------------------
#
# The Clos routes are pure permutations — semiring-agnostic by
# construction — so only the broadcast/reduce sides need algebra twins.
# Pad discipline carries over: every pad slot holds ``sr.zero`` (= 0.0
# for both shipped semirings — the max identity only because scores are
# nonnegative), so routed pads and free-slot fills stay correct under a
# max reduce exactly as they are under a sum.


def blocked_broadcast_semiring(arrs: dict, s, widths: tuple, xs: tuple,
                               total_len: int, sr: Semiring):
    """Semiring twin of :func:`blocked_broadcast`: expand a state
    vector into ``mul``-combined edge values. The 0/1 expansion einsum
    of the (+,×) path is really a lane-wise row SELECT — here it runs
    as an explicit repeat (w < 128: lane ``l`` takes grid row
    ``l // w``, the same layout ``_expand_matrix`` encodes) so ``mul``
    can be any binary op, not just multiply. Pad lanes carry weight 0
    → ``mul`` yields 0 on them (min of a nonnegative score with 0, or
    a product with 0)."""
    parts = []
    pos = 0
    for bi, (w, X) in enumerate(zip(widths, xs)):
        w_mat = arrs["out_weight"][bi]
        if w < 128:
            g = 128 // w
            s2t = lax.slice_in_dim(s, pos, pos + g * X).reshape(g, X)
            expanded = jnp.repeat(s2t.T, w, axis=1)   # [X, 128]
            v = sr.mul(expanded, w_mat)
            pos += g * X
        else:
            nb_pad = X * 128 // w
            rows = lax.slice_in_dim(s, pos, pos + nb_pad)
            expanded = jnp.broadcast_to(
                rows[:, None], (nb_pad, w // 128)).reshape(X, 1)
            v = sr.mul(jnp.broadcast_to(expanded, w_mat.shape), w_mat)
            pos += nb_pad
        parts.append(v.reshape(-1))
    used = sum(X * 128 for X in xs)
    parts.append(jnp.full((total_len - used,), sr.zero, dtype=s.dtype))
    return jnp.concatenate(parts)


def blocked_reduce_semiring(arrs: dict, y, widths: tuple, xs: tuple,
                            n_pos: int, total_len: int, sr: Semiring):
    """Semiring twin of :func:`blocked_reduce`: lane-segmented per-row
    ``reduce``. The w < 128 layout packs logical row ``r`` (lane-row
    ``x = r // g``, sub-row ``b = r % g``) into lanes
    ``[b·w, (b+1)·w)`` with z position ``b·X + x`` — so
    ``reshape(X, g, w) → reduce(-1) → transpose → flatten`` lands every
    row sum in exactly the slot the (+,×) einsum puts it in."""
    sums = []
    off = 0
    for bi, (w, X) in enumerate(zip(widths, xs)):
        y2 = lax.slice_in_dim(y, off, off + X * 128).reshape(X, 128)
        if w < 128:
            g = 128 // w
            z2 = sr.reduce(y2.reshape(X, g, w), axis=-1)   # [X, g]
            sums.append(z2.T.reshape(-1))
        else:
            nb_pad = X * 128 // w
            sums.append(sr.reduce(
                sr.reduce(y2, axis=-1).reshape(nb_pad, w // 128),
                axis=-1))
        off += X * 128
    sums.append(jnp.full((total_len - n_pos,), sr.zero, dtype=y.dtype))
    return jnp.concatenate(sums)


def spmv_routed_semiring(arrs: dict, static: RoutedStatic, s,
                         sr: Semiring):
    """One generalized sweep through the SAME compiled routed operator:
    broadcast → route → reduce → route-back under ``sr``, then the
    semiring tail. ``sr`` is static under jit, so the (+,×) branch
    compiles to exactly :func:`spmv_routed` and every other algebra
    reuses the operator's route plans untouched (routes are
    permutations — no algebra appears in them); the phases carry
    :func:`spmv_routed`'s named scopes. The delta engine's
    patched-matvec keys (``inv_row_scale``/``tail_*``) are a (+,×)
    normalization concept and never reach this path."""
    if sr.name == "plusmul":
        return spmv_routed(arrs, static, s)
    with jax.named_scope("broadcast"):
        x = blocked_broadcast_semiring(arrs, s, static.out_widths,
                                       static.out_xs, 1 << static.edge_e, sr)
    with jax.named_scope("edge_route"):
        y = _apply_route_jit(x, arrs["edge_stages"], static.edge_e,
                             static.edge_bits, static.pallas)
    with jax.named_scope("reduce"):
        z = blocked_reduce_semiring(arrs, y, static.in_widths, static.in_xs,
                                    static.in_n_pos, 1 << static.state_e, sr)
    with jax.named_scope("state_route"):
        base = _apply_route_jit(z, arrs["state_stages"], static.state_e,
                                static.state_bits, static.pallas)
    with jax.named_scope("tail"):
        return semiring_tail(sr, arrs, s, base)


@partial(jax.jit, static_argnames=("static", "sr", "num_iterations"))
def converge_routed_fixed_semiring(arrs, static: RoutedStatic, s0,
                                   sr: Semiring, num_iterations: int):
    """Fixed-iteration routed power iteration under a pluggable
    semiring (static: one compile per algebra per operator shape)."""
    return lax.fori_loop(
        0, num_iterations,
        lambda _, s: spmv_routed_semiring(arrs, static, s, sr), s0)


@partial(jax.jit, static_argnames=("static", "sr", "max_iterations",
                                   "accel_every"))
def converge_routed_adaptive_semiring(arrs, static: RoutedStatic, s0,
                                      sr: Semiring, tol: float = 1e-6,
                                      max_iterations: int = 100,
                                      accel_every: int = 0):
    """Adaptive routed converge under a pluggable semiring. Returns
    (scores, iterations_run, final_relative_delta)."""
    return adaptive_loop(
        lambda s: spmv_routed_semiring(arrs, static, s, sr), s0, tol,
        max_iterations, accel_every)


@partial(jax.jit, static_argnames=("static", "sr", "max_iterations"))
def converge_routed_topics(arrs, static: RoutedStatic, s0k, sr: Semiring,
                           tol: float = 1e-6, max_iterations: int = 100):
    """Topic-batched adaptive converge through ONE routed operator:
    vmap K state-order topic vectors ``s0k[K, 2^state_e]`` over the
    compiled sweep — the routing-plan build (the path's one-time cost,
    ``ptpu_routed_plan_build_seconds``) is amortized across all K
    contexts. while_loop batching select-masks per-topic updates, so
    each topic's trajectory is independent of its batch neighbors.
    Returns ``(scores[K, ·], iters[K], delta[K])``."""
    return jax.vmap(
        lambda s0: adaptive_loop(
            lambda s: spmv_routed_semiring(arrs, static, s, sr), s0,
            tol, max_iterations))(s0k)
