"""Multi-chip Clos-routed convergence: the permutation-network SpMV
sharded over a device mesh.

Where ``parallel.converge`` all-gathers the score vector and runs the
gather-SpMV per shard, this module shards the *routed* SpMV
(``ops.routed``): every lane-permutation stage of the Clos network is
row-local to a device, and only the level-0 perfect shuffle spans the
mesh — one ``lax.all_to_all`` forward and one back per route. Devices
own complete middle subnetworks (device d holds subnetworks
[d·128/D, (d+1)·128/D)), so every deeper level, the base, and the
bucket broadcast/reduce around the route are purely local compute. Per
iteration the ICI traffic is: 2 all-to-alls of the edge array, 2 of the
state vector, and O(1) psum scalars — no all-gather of scores at all.

Layout: global slot/state spaces are **device-major** — device d owns
the contiguous slot range [d·E2/D, (d+1)·E2/D) holding its buckets'
``[X, 128]`` blocks plus local zero padding, and likewise a contiguous
state slice. The route plans are computed over these global spaces by
the same planner as the single-chip path (the planner is layout-
agnostic: it routes whatever permutation the layout induces), and the
per-stage index arrays shard into per-device slices that stay aligned
with device ownership through every stage (lane perms are row-local;
the all_to_all exchanges exactly re-establish contiguity).

Constraints: the mesh size D must divide 128 (subnetwork ownership),
and the padded slot/state spaces are sized so each device's row count
is a multiple of 8 (Mosaic tile depth).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph import filter_edges
from ..ops.clos import (
    _lane_perm,
    _use_pallas,
    executor_stages,
    plan_route,
    route_core,
)
from ..ops.routed import (
    _bucketize_blocked,
    _ceil_pow2_exp,
    _expand_matrix,
    _initial_scores,
    _scores_for_nodes,
    _scores_from_nodes,
    blocked_broadcast,
    blocked_reduce,
)
from .converge import mesh_adaptive_loop, psum_dangling_and_damping
from .mesh import rows_axis, shard_map_norep

__all__ = [
    "ShardedRoutedOperator",
    "build_sharded_routed_operator",
    "sharded_routed_converge_fixed",
    "sharded_routed_converge_adaptive",
]


def sharded_apply_route(x_loc, stages_loc, e: int, bits: tuple, D: int,
                        pallas: bool):
    """Device-local body of a distributed route (inside shard_map).

    ``x_loc``: this device's contiguous slot slice (2^e / D elements).
    ``stages_loc``: per-device slices of the stage arrays.
    """
    if D == 1 or len(bits) == 1:
        return route_core(x_loc, stages_loc, 0, e, bits, pallas)
    E_loc = (1 << e) // D
    m = 1 << (e - 7)

    # level-0 input lane permutation (row-local)
    x = _lane_perm(x_loc, stages_loc[0], pallas)
    # perfect shuffle across the mesh: [m, 128] -> [128, m], sharded
    x = x.reshape(m // D, 128)
    x = lax.all_to_all(x, rows_axis, split_axis=1, concat_axis=0,
                       tiled=True)                      # [m, 128//D]
    x = x.T.reshape(E_loc)  # this device's subnetworks, contiguous

    # middle levels: batched, fully local
    x = route_core(x, stages_loc, 1, e - 7, bits[1:], pallas)

    # inverse shuffle
    x = x.reshape(128 // D, m).T                        # [m, 128//D]
    x = lax.all_to_all(x, rows_axis, split_axis=0, concat_axis=1,
                       tiled=True)                      # [m//D, 128]
    # level-0 output lane permutation
    x = _lane_perm(x.reshape(E_loc), stages_loc[-1], pallas)
    return x.reshape(E_loc)


@dataclass
class ShardedRoutedOperator:
    """Per-device blocked layouts + global route plans, device-major."""

    n: int
    n_valid: int
    nnz: int
    num_shards: int
    # uniform per-device bucket geometry
    out_widths: tuple
    out_xs: tuple             # per bucket: lane-rows per device
    out_weight: list          # per bucket: [D, X, 128] float64
    in_widths: tuple
    in_xs: tuple
    in_n_pos: int             # per-device z positions (pads included)
    n_state_local: int        # per-device state slice length (N2 // D)
    state_to_node: np.ndarray  # [N2] global state slot -> node id (-1 dead)
    edge_e: int
    edge_bits: tuple
    edge_stages: list         # flat uint8 [E2] each
    state_e: int
    state_bits: tuple
    state_stages: list
    valid: np.ndarray         # [N2] f32, device-major state order
    dangling: np.ndarray

    @property
    def n_state(self) -> int:
        return 1 << self.state_e

    def initial_scores(self, initial: float, dtype=np.float32) -> np.ndarray:
        return _initial_scores(self.valid, initial, dtype)

    def scores_for_nodes(self, state_scores: np.ndarray) -> np.ndarray:
        return _scores_for_nodes(self.state_to_node, self.n, state_scores)

    def scores_from_nodes(self, node_scores: np.ndarray,
                          dtype=np.float32) -> np.ndarray:
        """Node-order vector → device-major state order (warm start)."""
        return _scores_from_nodes(self.state_to_node, self.valid,
                                  node_scores, dtype)

    def save(self, path) -> None:
        """Persist the compiled device-major operator (uncompressed .npz,
        atomic) so the one-time routing-plan compilation is reusable
        across runs. The layout is D-specific: ``load`` refuses a
        different shard count rather than silently permuting scores."""
        from ..ops.routed import save_operator_npz

        save_operator_npz(self, path)

    @classmethod
    def load(cls, path, num_shards=None) -> "ShardedRoutedOperator":
        from ..ops.routed import load_operator_npz

        with np.load(path) as z:
            op = load_operator_npz(cls, z)
        if num_shards is not None and op.num_shards != num_shards:
            raise ValueError(
                f"cached operator was compiled for "
                f"num_shards={op.num_shards}, requested {num_shards}")
        return op

    def device_arrays(self, dtype=jnp.float32, alpha: float = 0.0,
                      pretrust=None) -> dict:
        """Stacked pytree with leading shard axis, for shard_map."""
        D = self.num_shards
        if pretrust is None:
            pretrust = self.valid.astype(np.float64) / max(self.n_valid, 1)

        def stages(flat, bits, route):
            # the local route_core runs bits[1:] from stage 1 when the
            # level-0 shuffle crosses the mesh (sharded_apply_route)
            si, local = ((0, bits) if D == 1 or len(bits) == 1
                         else (1, bits[1:]))
            staged = executor_stages((jnp.asarray(s) for s in flat), local,
                                     route, si=si)
            return tuple(s.reshape(D, -1) for s in staged)

        return {
            "out_weight": tuple(jnp.asarray(w, dtype=dtype)
                                for w in self.out_weight),
            "out_expand": tuple(
                jnp.asarray(
                    np.broadcast_to(_expand_matrix(w, np.float32),
                                    (D, 128 // w, 128)).copy(), dtype=dtype)
                if w < 128 else jnp.zeros((D, 1, 1), dtype=dtype)
                for w in self.out_widths),
            "in_reduce": tuple(
                jnp.asarray(
                    np.broadcast_to(_expand_matrix(w, np.float32),
                                    (D, 128 // w, 128)).copy(), dtype=dtype)
                if w < 128 else jnp.zeros((D, 1, 1), dtype=dtype)
                for w in self.in_widths),
            "edge_stages": stages(self.edge_stages, self.edge_bits, "edge"),
            "state_stages": stages(self.state_stages, self.state_bits,
                                   "state"),
            "valid": jnp.asarray(
                self.valid.reshape(D, -1), dtype=dtype),
            "dangling": jnp.asarray(
                self.dangling.reshape(D, -1), dtype=dtype),
            "pretrust": jnp.asarray(
                np.asarray(pretrust).reshape(D, -1), dtype=dtype),
            "alpha": jnp.asarray(
                np.full((D, 1), float(alpha)), dtype=dtype),
        }


def build_sharded_routed_operator(
    n, src, dst, val, valid=None, num_shards: int = 1, min_width: int = 8,
    prefer_native: bool = True,
) -> ShardedRoutedOperator:
    """Filter + normalize an edge list and compile a device-major routing
    program for ``num_shards`` devices (must divide 128)."""
    D = num_shards
    assert D >= 1 and 128 % D == 0, "num_shards must divide 128"
    src, dst, weight, valid_mask, dangling = filter_edges(
        n, src, dst, val, valid)

    # nodes striped across devices by id; per-device blocked sides
    out_sides, in_sides = [], []
    for d in range(D):
        m_out = (src % D) == d
        out_sides.append(_bucketize_blocked(
            n, src[m_out], dst[m_out], weight[m_out], min_width))
        m_in = (dst % D) == d
        in_sides.append(_bucketize_blocked(
            n, dst[m_in], src[m_in], weight[m_in], min_width))

    def unify(sides):
        """Common width set + per-width max X across devices."""
        widths = sorted({w for s in sides for w in s.widths})
        xs = []
        for w in widths:
            xs.append(max(
                (s.xs[s.widths.index(w)] if w in s.widths else 0)
                for s in sides))
        # every device's X must be a multiple of 8 already; keep max
        return tuple(widths), tuple(int(x) for x in xs)

    out_widths, out_xs = unify(out_sides)
    in_widths, in_xs = unify(in_sides)
    out_slots_dev = sum(x * 128 for x in out_xs)
    in_slots_dev = sum(x * 128 for x in in_xs)

    # global edge-slot space: device-major, 2^edge_e total. Each device's
    # slice must hold its buckets and split into ≥8 lane-rows, and the
    # level-0 row space m = E2/128 must divide by D.
    floor_e = max(7, 10 + (D - 1).bit_length())
    edge_e = _ceil_pow2_exp(max(out_slots_dev, in_slots_dev, 1) * D, floor_e)

    def side_slots(sides, widths, xs, base_of_dev):
        """Map every edge to its global slot under the unified geometry."""
        slots = []
        for d, s in enumerate(sides):
            # bucket base offsets under unified geometry
            base = {}
            off = 0
            for w, X in zip(widths, xs):
                base[w] = off
                off += X * 128
            # remap this device's local slots bucket-by-bucket
            loc = np.asarray(s.edge_slot)
            out = np.empty(len(loc), dtype=np.int64)
            for w, sb, X_d in zip(s.widths, s.slot_base, s.xs):
                nsl = X_d * 128
                m = (loc >= sb) & (loc < sb + nsl)
                out[m] = base_of_dev(d) + base[w] + (loc[m] - sb)
            slots.append(out)
        return slots

    E2 = 1 << edge_e
    dev_stride = E2 // D
    out_slot_l = side_slots(out_sides, out_widths, out_xs,
                            lambda d: d * dev_stride)
    in_slot_l = side_slots(in_sides, in_widths, in_xs,
                           lambda d: d * dev_stride)

    # weights under unified geometry
    out_weight = []
    for w, X in zip(out_widths, out_xs):
        wm = np.zeros((D, X, 128), dtype=np.float64)
        for d, s in enumerate(out_sides):
            if w in s.widths:
                bi = s.widths.index(w)
                wm[d, : s.xs[bi]] = s.weight[bi]
        out_weight.append(wm)

    # per-device state layout: out-side positions (unified geometry),
    # then the device's out-edge-less nodes, then padding
    def unified_pos(sides, widths, xs):
        """Per device: node ids and their positions under unified bases."""
        pos_base = {}
        off = 0
        for w, X in zip(widths, xs):
            g = (128 // w) if w < 128 else 1
            pos_base[w] = off
            off += g * X if w < 128 else X * 128 // w
        n_pos_dev = off
        out = []
        for s in sides:
            nodes_l, pos_l = [], []
            for bi, w in enumerate(s.widths):
                X_d = s.xs[bi]
                rp = s.row_pos[bi] - s.pos_base[bi]  # local grid position
                if w < 128:
                    # re-express column-major position under unified X
                    g = 128 // w
                    i, x = rp // X_d, rp % X_d
                    X_u = xs[widths.index(w)]
                    rp = i * X_u + x
                nodes_l.append(s.row_nodes[bi])
                pos_l.append(pos_base[w] + rp)
            out.append((np.concatenate(nodes_l) if nodes_l else
                        np.zeros(0, dtype=np.int64),
                        np.concatenate(pos_l) if pos_l else
                        np.zeros(0, dtype=np.int64)))
        return out, n_pos_dev

    out_np, out_pos_dev = unified_pos(out_sides, out_widths, out_xs)
    in_np, in_pos_dev = unified_pos(in_sides, in_widths, in_xs)

    has_out = np.zeros(n, dtype=bool)
    for nodes, _ in out_np:
        has_out[nodes] = True
    rest_per_dev = [np.nonzero((~has_out)
                               & ((np.arange(n) % D) == d))[0]
                    for d in range(D)]
    state_need = max(out_pos_dev + max(len(r) for r in rest_per_dev),
                     in_pos_dev, 1)
    state_e = _ceil_pow2_exp(state_need * D, floor_e)
    N2 = 1 << state_e
    s_stride = N2 // D

    state_to_node = np.full(N2, -1, dtype=np.int64)
    for d in range(D):
        nodes, pos = out_np[d]
        state_to_node[d * s_stride + pos] = nodes
        r = rest_per_dev[d]
        state_to_node[d * s_stride + out_pos_dev:
                      d * s_stride + out_pos_dev + len(r)] = r

    # --- edge route ------------------------------------------------------
    perm = np.full(E2, -1, dtype=np.int64)
    all_in = np.concatenate(in_slot_l) if in_slot_l else np.zeros(0, np.int64)
    all_out = (np.concatenate(out_slot_l) if out_slot_l
               else np.zeros(0, np.int64))
    # both sides enumerate the SAME filtered edges, each in its own
    # device-subset order — align through global edge ids
    eid = np.arange(len(src))
    out_eid = np.concatenate([eid[(src % D) == d] for d in range(D)])
    in_eid = np.concatenate([eid[(dst % D) == d] for d in range(D)])
    out_slot_of_eid = np.empty(len(src), dtype=np.int64)
    out_slot_of_eid[out_eid] = all_out
    perm[all_in] = out_slot_of_eid[in_eid]

    src_used = np.zeros(E2, dtype=bool)
    src_used[all_out] = True
    free_src = np.nonzero(~src_used)[0]
    need = np.nonzero(perm < 0)[0]
    perm[need] = free_src[: len(need)]
    plan = plan_route(perm.astype(np.int32), prefer_native=prefer_native)

    # --- state route -----------------------------------------------------
    node_in_pos = np.full(n, -1, dtype=np.int64)
    for d in range(D):
        nodes, pos = in_np[d]
        node_in_pos[nodes] = d * s_stride + pos
    sperm = np.full(N2, -1, dtype=np.int64)
    live = state_to_node >= 0
    live_slots = np.nonzero(live)[0]
    live_nodes = state_to_node[live_slots]
    with_in = node_in_pos[live_nodes] >= 0
    sperm[live_slots[with_in]] = node_in_pos[live_nodes[with_in]]
    sp_used = np.zeros(N2, dtype=bool)
    sp_used[sperm[sperm >= 0]] = True
    free_zero = np.nonzero(~sp_used)[0]
    need = np.nonzero(sperm < 0)[0]
    sperm[need] = free_zero[: len(need)]
    splan = plan_route(sperm.astype(np.int32), prefer_native=prefer_native)

    valid_state = np.zeros(N2, dtype=np.float32)
    valid_state[live_slots] = valid_mask[live_nodes].astype(np.float32)
    dangling_state = np.zeros(N2, dtype=np.float32)
    dangling_state[live_slots] = dangling[live_nodes].astype(np.float32)

    return ShardedRoutedOperator(
        n=n,
        n_valid=int(valid_mask.sum()),
        nnz=len(src),
        num_shards=D,
        out_widths=out_widths,
        out_xs=out_xs,
        out_weight=out_weight,
        in_widths=in_widths,
        in_xs=in_xs,
        in_n_pos=in_pos_dev,
        n_state_local=s_stride,
        state_to_node=state_to_node,
        edge_e=plan.e,
        edge_bits=plan.bits,
        edge_stages=plan.stages,
        state_e=splan.e,
        state_bits=splan.bits,
        state_stages=splan.stages,
        valid=valid_state,
        dangling=dangling_state,
    )


def _local_routed_spmv(arrs, s_loc, n_valid, cfg):
    """Per-device routed SpMV body (inside shard_map)."""
    (out_widths, out_xs, in_widths, in_xs, in_n_pos, edge_e, edge_bits,
     state_e, state_bits, D, pallas) = cfg
    x = blocked_broadcast(arrs, s_loc, out_widths, out_xs,
                          (1 << edge_e) // D)
    y = sharded_apply_route(x, arrs["edge_stages"], edge_e, edge_bits, D,
                            pallas)
    z = blocked_reduce(arrs, y, in_widths, in_xs, in_n_pos,
                       (1 << state_e) // D)
    base = sharded_apply_route(z, arrs["state_stages"], state_e, state_bits,
                               D, pallas)
    return psum_dangling_and_damping(arrs, s_loc, base, n_valid)


def _cfg(op: ShardedRoutedOperator, pallas: bool):
    return (op.out_widths, op.out_xs, op.in_widths, op.in_xs, op.in_n_pos,
            op.edge_e, op.edge_bits, op.state_e, op.state_bits,
            op.num_shards, pallas)


@lru_cache(maxsize=32)
def _fixed_fn(mesh: Mesh, n_valid: float, num_iterations: int, cfg):
    def run(arrs, s):
        arrs = jax.tree.map(lambda x: x[0], arrs)

        def body(_, s_loc):
            return _local_routed_spmv(arrs, s_loc, n_valid, cfg)

        return lax.fori_loop(0, num_iterations, body, s)

    shmapped = shard_map_norep(
        run, mesh,
        (P(rows_axis), P(rows_axis)),
        P(rows_axis),
    )
    return jax.jit(shmapped)


@lru_cache(maxsize=32)
def _adaptive_fn(mesh: Mesh, n_valid: float, tol: float,
                 max_iterations: int, cfg):
    def run(arrs, s):
        arrs = jax.tree.map(lambda x: x[0], arrs)
        return mesh_adaptive_loop(
            lambda s_loc: _local_routed_spmv(arrs, s_loc, n_valid, cfg),
            s, tol, max_iterations,
        )

    shmapped = shard_map_norep(
        run, mesh,
        (P(rows_axis), P(rows_axis)),
        (P(rows_axis), P(), P()),
    )
    return jax.jit(shmapped)


def place_sharded_routed(op: ShardedRoutedOperator, mesh: Mesh,
                         dtype=jnp.float32, alpha: float = 0.0) -> dict:
    """Build the stacked device pytree ONCE and place it on the mesh.
    Callers that converge repeatedly (the checkpointed driver,
    benchmarks) should hoist this — the operator's stage/weight arrays
    are gigabytes at scale and must not be re-staged per call."""
    sharding = NamedSharding(mesh, P(rows_axis))
    return jax.tree.map(lambda x: jax.device_put(x, sharding),
                        op.device_arrays(dtype, alpha=alpha))


def _resolve_routed(sop, mesh: Mesh, dtype, alpha: float):
    """Accept a ShardedRoutedOperator or an (operator, placed_arrs) pair."""
    if isinstance(sop, tuple):
        return sop[0], sop[1]
    return sop, place_sharded_routed(sop, mesh, dtype, alpha)


def _place_scores(mesh: Mesh, s0):
    return jax.device_put(jnp.asarray(s0).reshape(-1),
                          NamedSharding(mesh, P(rows_axis)))


def sharded_routed_converge_fixed(
    op, s0, num_iterations: int, mesh: Mesh,
    alpha: float = 0.0, dtype=jnp.float32, pallas: bool | None = None,
):
    """Fixed-iteration sharded routed power iteration. Returns the full
    state-order score vector (use ``op.scores_for_nodes``). ``op``: a
    ShardedRoutedOperator, or (operator, placed_arrs) with placed_arrs
    from :func:`place_sharded_routed` to skip per-call staging."""
    if pallas is None:
        pallas = _use_pallas()
    meta, arrs = _resolve_routed(op, mesh, dtype, alpha)
    s = _place_scores(mesh, jnp.asarray(s0, dtype))
    cfg = _cfg(meta, pallas)
    from ..ops.converge import timed_converge

    # the lru_cache key of _fixed_fn IS the jit-cache identity here
    out = timed_converge(
        "sharded-routed", meta.n, int(meta.nnz),
        ("sharded-fixed", mesh, cfg, str(jnp.dtype(dtype)),
         int(num_iterations)),
        lambda: _fixed_fn(mesh, float(meta.n_valid), int(num_iterations),
                          cfg)(arrs, s),
        fixed_iterations=num_iterations)
    return out.reshape(-1)


def sharded_routed_converge_adaptive(
    op, s0, mesh: Mesh, tol: float = 1e-6,
    max_iterations: int = 100, alpha: float = 0.0, dtype=jnp.float32,
    pallas: bool | None = None,
):
    """Tolerance-based sharded routed power iteration.
    Returns (state_scores, iterations, final_relative_delta). ``op`` as
    in :func:`sharded_routed_converge_fixed`."""
    if pallas is None:
        pallas = _use_pallas()
    meta, arrs = _resolve_routed(op, mesh, dtype, alpha)
    s = _place_scores(mesh, jnp.asarray(s0, dtype))
    cfg = _cfg(meta, pallas)
    from ..ops.converge import timed_converge

    # tol joins the signature here (unlike the single-device backends,
    # where it is traced): _adaptive_fn bakes it into the shmapped
    # function, so a new tol legitimately compiles
    scores, iters, delta = timed_converge(
        "sharded-routed", meta.n, int(meta.nnz),
        ("sharded-adaptive", mesh, cfg, str(jnp.dtype(dtype)),
         float(tol), int(max_iterations)),
        lambda: _adaptive_fn(mesh, float(meta.n_valid), float(tol),
                             int(max_iterations), cfg)(arrs, s))
    return scores.reshape(-1), iters, delta
