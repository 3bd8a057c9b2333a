"""Production distributed D-iteration engine (TPU-native adaptation).

This is the deployable counterpart of :mod:`repro.core.simulator`
(DESIGN.md §3).  The paper's point-to-point, one-node-at-a-time scheme is
mapped onto JAX-native constructs:

* **shard_map over a ``pid`` device axis** — each device plays one PID.
* **Bucket-granular state** — nodes are packed into fixed-size buckets
  (the ``GraphStore`` engine-layout view, DESIGN.md §7, which graph
  deltas patch row-by-row); every device owns a *fixed* number of
  bucket rows (static shapes), some of which are inert headroom.  The
  :mod:`repro.balance` control plane moves whole buckets between devices
  (``MovePlan`` kind ``bucket`` executed by ``BucketMoveExecutor``) by
  permuting the bucket-indexed arrays in-graph (``jnp.take`` on the sharded
  axis lowers to collective-permute / all-gather under SPMD), so load can
  move without any reshaping — this is also the elastic-scaling path.  The
  engine takes any ``Rebalancer`` policy; the legacy ``dynamic`` flag maps
  to the paper-exact ``slope_ema``.
* **Frontier-batched local diffusion** — every local node above the
  threshold diffuses simultaneously (a valid D-iteration schedule); the push
  becomes gather → multiply → ``segment_sum``.
* **reduce-scatter fluid exchange** — remote contributions accumulate in a
  per-device full-length outbox; one ``psum_scatter`` over the ``pid`` axis
  delivers every device exactly the fluid destined to its slots.  The paper's
  ``s_k > r_k/2`` rule decides *when* the exchange happens (evaluated
  in-graph with any-device-fires semantics, so the collective stays
  congruent across devices).
* **Threshold schedule** — per-device T with γ decay and the paper's
  receive-time re-seed ``T := min(T·(r+recv)/r, recv)``.

The same engine is lowered in the multi-pod dry-run (launch/dryrun.py) as the
solver "architecture" entry, proving the collective schedule compiles on the
production meshes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.balance.executors import BucketMoveExecutor
from repro.balance.plan import MovePlan
from repro.balance.policies import Rebalancer, make_rebalancer
from repro.balance.signals import LoadSignal
from repro.obs import count_add
from repro.parallel.compat import shard_map

from .graph import CSRGraph  # noqa: F401  (public signature type)

__all__ = [
    "EngineConfig",
    "EngineArrays",
    "EngineState",
    "DistributedEngine",
    "build_engine_arrays",
]

GAMMA = 1.2


@dataclasses.dataclass
class EngineConfig:
    k: int  # devices on the 'pid' axis
    target_error: float
    eps: float
    buckets_per_dev: int = 8  # owned bucket rows per device (incl. headroom)
    headroom: int = 2  # inert bucket rows per device for load moves
    max_inner: int = 8  # max local rounds between exchanges
    gamma: float = GAMMA
    dynamic: bool = False  # enable the control plane (slope_ema policy)
    policy: Optional[str] = None  # repro.balance policy name (overrides
    # ``dynamic``): slope_ema | cost_refresh | hysteresis
    signal: str = "residual"  # rebalancing signal: residual | edge-ops
    eta: float = 0.5
    z: int = 10
    chunk_rounds: int = 4  # exchange cycles per jitted chunk
    max_chunks: int = 4096
    dtype: jnp.dtype = jnp.float32
    diffusion_backend: str = "segment_sum"  # per-edge scatter | "bsr":
    # bucket-tiled dense blocks (MXU path; Pallas gather kernel on TPU,
    # einsum + segment-sum elsewhere)
    pallas_interpret: bool = False  # force the Pallas tile kernel through
    # the interpreter off-TPU (parity tests only — emulation speed)
    pallas_buffer_depth: int = 1  # tile-pool DMA pipeline depth for the
    # gather kernel (1 = automatic BlockSpec pipelining; >= 2 = manual
    # async-copy ring; bit-identical results either way)


@dataclasses.dataclass
class EngineArrays:
    """Static bucket-major tensors fed to the engine (device-shardable).

    R = K * buckets_per_dev rows, S = bucket_size slots per row,
    E = edge capacity per row.  Row r is owned by device r // buckets_per_dev.
    ``pos_of_bucket`` maps a *stable bucket id* to its current row; edge
    destinations are stored as (stable bucket id, in-bucket slot) so bucket
    moves only update the small replicated position map.
    """

    f0: np.ndarray  # [R, S] initial fluid
    w: np.ndarray  # [R, S] selection weights (0 = inert slot)
    src_slot: np.ndarray  # [R, E] in-bucket source slot of each edge
    dst_bucket: np.ndarray  # [R, E] destination stable bucket id
    dst_slot: np.ndarray  # [R, E] destination in-bucket slot
    wgt: np.ndarray  # [R, E] edge weight (0 = padding edge)
    pos_of_bucket: np.ndarray  # [R] stable bucket id -> initial row
    node_of_slot: np.ndarray  # [R, S] global node id or -1 (initial rows)
    n: int
    n_edges: int
    # BSR tiling of the bucket-local edges (diffusion_backend="bsr"):
    # ``tiles[r, t]`` is the dense [S, S] block pushing fluid from the bucket
    # currently at row ``r`` into stable bucket ``tile_dst[r, t]``
    # (``tiles[r, t][dst_slot, src_slot] = weight``; padding tiles are zero
    # and point at bucket 0 — harmless).  Row-indexed on purpose: a bucket
    # move permutes whole tile groups with the same ``jnp.take`` that moves
    # f/h/w, while ``tile_dst`` stores *stable* ids and never changes.
    tiles: Optional[np.ndarray] = None  # [R, T, S, S]
    tile_dst: Optional[np.ndarray] = None  # [R, T] int32
    slot_out_deg: Optional[np.ndarray] = None  # [R, S] int32 real edges per
    # slot — the bsr path's §2.3 op counter (no per-edge gather needed)

    @property
    def n_rows(self) -> int:
        return int(self.f0.shape[0])

    @property
    def bucket_size(self) -> int:
        return int(self.f0.shape[1])

    @property
    def edge_cap(self) -> int:
        return int(self.wgt.shape[1])


def build_engine_arrays(
    g,
    b: np.ndarray,
    cfg: EngineConfig,
    order: Optional[np.ndarray] = None,
) -> EngineArrays:
    """Bucketize (P, B) into the engine's fixed-shape layout.

    ``g`` is a :class:`repro.graph.GraphStore` or a :class:`CSRGraph`
    (wrapped into a throwaway store).  The graph-derived half comes
    from the store's cached **engine-layout view** — so after
    ``store.apply_delta`` only dirty rows/tiles were recomputed — and
    only the RHS-dependent ``f0`` is materialized here.

    Real buckets fill ``buckets_per_dev - headroom`` rows per device; the
    remaining rows are inert landing slots for dynamic bucket moves.
    """
    from repro.graph import GraphStore

    store = g if isinstance(g, GraphStore) else GraphStore.from_csr(g)
    lay = store.engine_layout(
        cfg.k, cfg.buckets_per_dev, cfg.headroom,
        tiled=cfg.diffusion_backend != "segment_sum",
        dtype=np.dtype(cfg.dtype), order=order,
    )
    f0 = np.zeros((lay.n_rows, lay.bucket_size), dtype=np.float64)
    valid = lay.node_of_slot >= 0
    f0[valid] = np.asarray(b, dtype=np.float64)[lay.node_of_slot[valid]]
    return EngineArrays(
        tiles=lay.tiles,
        tile_dst=lay.tile_dst,
        slot_out_deg=lay.slot_out_deg,
        f0=f0,
        w=lay.w,
        src_slot=lay.src_slot,
        dst_bucket=lay.dst_bucket,
        dst_slot=lay.dst_slot,
        wgt=lay.wgt,
        pos_of_bucket=lay.pos_of_bucket,
        node_of_slot=lay.node_of_slot,
        n=lay.n,
        n_edges=lay.n_edges,
    )


def _tile_push_stable(
    tiles: jax.Array,  # [B_loc, T, S, S] this device's tile groups
    tile_dst: jax.Array,  # [B_loc, T] stable destination bucket ids
    sent: jax.Array,  # [B_loc, S] masked fluid leaving this round
    r_total: int,
    *,
    use_pallas: bool,
    interpret: bool = False,
    buffer_depth: int = 1,
    row_armed: Optional[jax.Array] = None,  # [B_loc] bool
) -> jax.Array:
    """delta[bid] = sum of tile @ sent over tiles targeting stable bucket bid.

    Two implementations of the same contraction:

    * Pallas (TPU / forced-interpret): the tiles stay in their row-owned
      pool, and :func:`bsr_gather_spmm_pallas` walks the round's visit list
      (:func:`_armed_visits`): the tiles of the rows that send fluid
      (``row_armed``), in destination order.  A row with nothing selected
      sends an all-zero ``sent`` row, so its tiles would add exact zeros:
      they cost no grid step.  The buckets no visit reaches are masked to
      zero (the kernel leaves their output blocks uninitialised).  With
      ``row_armed`` None every tile is a visit, the grid spans the whole
      pool, and the result is the same up to the sign of a zero.
    * einsum + segment-sum: XLA batched-matmul path, the CPU default.

    Padding tiles are all-zero and point at bucket 0 — they contribute
    nothing either way.
    """
    b_loc, t_cap, s, _ = tiles.shape
    if use_pallas:
        from repro.kernels.diffusion import bsr_gather_spmm_pallas

        every_tile = row_armed is None
        if every_tile:
            row_armed = jnp.ones(b_loc, bool)
        visit_block, visit_row, visit_col, n_visits, reached = _armed_visits(
            tile_dst, row_armed, r_total)
        out = bsr_gather_spmm_pallas(
            tiles.reshape(-1, s, s), visit_block, visit_row, visit_col,
            sent[:, :, None], r_total, bs=s, interpret=interpret,
            buffer_depth=buffer_depth,
            n_visits=None if every_tile else n_visits,
        )
        return jnp.where(reached[:, None], out[..., 0],
                         jnp.zeros_like(out[..., 0]))
    partial = jnp.einsum("btij,bj->bti", tiles, sent)
    return jax.ops.segment_sum(
        partial.reshape(-1, s), tile_dst.reshape(-1), num_segments=r_total
    )


def _armed_visits(tile_dst: jax.Array, row_armed: jax.Array, r_total: int):
    """The round's visit list for the gather kernel.

    Returns ``(visit_block, visit_row, visit_col, n_visits, reached)``: the
    first ``n_visits`` entries are the pool slots of the armed rows, sorted
    by destination bucket, ties in pool order (the order an all-slot push
    accumulates them in); the other slots follow, sorted the same way.
    ``visit_row`` is each slot's destination, ``visit_col`` its source row,
    and ``reached`` marks the buckets that at least one visit targets.

    One stable sort on "not armed, then destination" over the pool builds
    the list, and the reached buckets are a one-hot product on the MXU: a
    TPU gathers and scatters scalars one at a time.
    """
    from repro.kernels.diffusion.ops import _lane_split_one_hot

    t_cap = tile_dst.shape[1]
    dst = tile_dst.reshape(-1)
    armed = jnp.broadcast_to(row_armed[:, None], tile_dst.shape).reshape(-1)
    key = dst + jnp.where(armed, 0, r_total).astype(dst.dtype)
    key, visit_block = jax.lax.sort(
        (key, jnp.arange(dst.shape[0], dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    visit_row = jnp.where(key < r_total, key, key - r_total)
    n_visits = t_cap * jnp.sum(row_armed, dtype=jnp.int32)
    hi, lo = _lane_split_one_hot(dst, r_total)
    visits_in_row = jax.lax.dot_general(
        hi, lo * armed[:, None].astype(jnp.bfloat16),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    reached = visits_in_row.reshape(-1)[:r_total] > 0
    return (visit_block, visit_row, visit_block // t_cap, n_visits,
            reached)


def _tile_engine_edges(
    src_slot: np.ndarray,  # [R, E]
    dst_bucket: np.ndarray,  # [R, E] stable bucket ids
    dst_slot: np.ndarray,  # [R, E]
    wgt: np.ndarray,  # [R, E] (0 = padding)
    s: int,
    dtype: np.dtype,
) -> Tuple[np.ndarray, np.ndarray]:
    """Group each row's edge buffer into dense [S, S] per-destination tiles.

    The tile capacity T is the max distinct destination buckets of any row
    (shared across rows/devices so shard_map sees one static shape); unused
    tile slots stay zero with ``tile_dst = 0``.
    """
    r = src_slot.shape[0]
    groups = []
    t_max = 1
    for row in range(r):
        mask = wgt[row] != 0
        uniq = np.unique(dst_bucket[row][mask])
        groups.append(uniq)
        t_max = max(t_max, uniq.shape[0])
    tiles = np.zeros((r, t_max, s, s), dtype=dtype)  # compute dtype: the
    # engine casts anyway, and a float64 intermediate doubles peak memory
    tile_dst = np.zeros((r, t_max), dtype=np.int32)
    for row in range(r):
        mask = wgt[row] != 0
        db = dst_bucket[row][mask]
        ds = dst_slot[row][mask]
        ss = src_slot[row][mask]
        wv = wgt[row][mask]
        uniq = groups[row]
        tile_dst[row, : uniq.shape[0]] = uniq
        t_of_edge = np.searchsorted(uniq, db)
        np.add.at(tiles, (row, t_of_edge, ds, ss), wv)
    return tiles, tile_dst


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    """Sharded solver state.

    ``f``/``h`` are [R, S] sharded on rows; ``outbox`` is [K, R*S] sharded on
    its first axis (each device holds ITS full-length outbox); ``t`` is [K]
    and ``ops`` [K, 2] (each device's §2.3 push count as the two uint32
    words of :func:`repro.obs.count_add`, exact past 2**31), both sharded
    one-per-device; ``pos_of_bucket`` is replicated.  ``armed`` [K, 2]
    counts each device's armed tiles the same way: the tiles of the rows
    that sent fluid, summed over local rounds — the gather kernel's grid
    steps on the bsr path, zero on the per-edge path.
    """

    f: jax.Array
    h: jax.Array
    outbox: jax.Array
    t: jax.Array
    pos_of_bucket: jax.Array
    ops: jax.Array
    rounds: jax.Array
    armed: jax.Array


# ---------------------------------------------------------------------- #
# compiled-program reuse across engine instances
#
# Engines are rebuilt wholesale on graph updates, checkpoint restores and
# driver re-seeds (SolverSession treats the engine as disposable), but the
# traced chunk program depends only on the STATIC build inputs below — not
# on the array contents.  Without this cache every rebuilt engine carried
# a fresh ``@jax.jit`` closure and re-paid the full XLA compile (~seconds)
# even though the HLO was bit-identical, which made serving a graph-update
# stream ~50× slower than the math requires.  Meshes are interned too, so
# shardings stay identity-equal across rebuilds and device buffers can be
# reused as-is.
# ---------------------------------------------------------------------- #
_MESH_CACHE: dict = {}
_CHUNK_CACHE: dict = {}


def device_ops(ops) -> np.ndarray:
    """Each device's count, as int64, from the [K, 2] word pairs of
    ``EngineState.ops`` (or ``.armed``)."""
    words = np.asarray(ops).astype(np.int64)
    return words[:, 0] << 32 | words[:, 1]


def _shared_mesh(devs, axis: str) -> Mesh:
    key = (tuple(d.id for d in devs), axis)
    hit = _MESH_CACHE.get(key)
    if hit is None:
        hit = Mesh(np.array(devs), (axis,))
        _MESH_CACHE[key] = hit
    return hit


@jax.jit
def _repart(state: EngineState, row_perm, new_pos, operands):
    take = lambda x: jnp.take(x, row_perm, axis=0)
    new_state = EngineState(
        f=take(state.f), h=take(state.h), outbox=state.outbox,
        t=state.t, pos_of_bucket=new_pos, ops=state.ops,
        rounds=state.rounds, armed=state.armed)
    return new_state, tuple(take(x) for x in operands)


class DistributedEngine:
    """shard_map production solver for ``X = P X + B``."""

    def __init__(
        self,
        arrays: EngineArrays,
        cfg: EngineConfig,
        mesh: Optional[Mesh] = None,
        axis: str = "pid",
        rebalancer: Optional[Rebalancer] = None,
    ):
        if cfg.signal not in ("residual", "edge-ops"):
            raise ValueError(
                f"unknown rebalancing signal {cfg.signal!r}; expected "
                "'residual' or 'edge-ops'"
            )
        if cfg.diffusion_backend not in ("segment_sum", "bsr"):
            raise ValueError(
                f"unknown diffusion backend {cfg.diffusion_backend!r}; "
                "expected 'segment_sum' or 'bsr'"
            )
        if cfg.diffusion_backend == "bsr" and arrays.tiles is None:
            raise ValueError(
                "diffusion_backend='bsr' needs tiled arrays — build them "
                "with build_engine_arrays(..., cfg) using the same config"
            )
        self.a = arrays
        self.cfg = cfg
        self.axis = axis
        if mesh is None:
            devs = jax.devices()[: cfg.k]
            assert len(devs) == cfg.k, (
                f"need {cfg.k} devices for the pid axis, have "
                f"{len(jax.devices())}"
            )
            mesh = _shared_mesh(devs, axis)
        self.mesh = mesh
        self.row_sharding = NamedSharding(mesh, P(axis))
        self.rep_sharding = NamedSharding(mesh, P())
        if rebalancer is not None:
            self.rebalancer: Optional[Rebalancer] = rebalancer
        elif cfg.policy or cfg.dynamic:
            self.rebalancer = make_rebalancer(
                cfg.policy or "slope_ema", k=cfg.k,
                target_error=cfg.target_error, eta=cfg.eta, z=cfg.z,
                unit="bucket",
            )
        else:
            self.rebalancer = None
        # chaos/straggler injection hook: a [K] factor multiplying the
        # load signal the control plane sees (None = healthy).  A real
        # straggling device cannot be slowed from here, but its *signal*
        # can — the controller then sheds load exactly as it would in
        # production (repro.chaos.SessionInjector sets this).
        self.load_scale: Optional[np.ndarray] = None
        chunk_key = (
            axis, tuple(d.id for d in self.mesh.devices.flat),
            cfg.k, cfg.buckets_per_dev, arrays.bucket_size,
            arrays.n_rows, cfg.diffusion_backend, cfg.pallas_interpret,
            cfg.pallas_buffer_depth, cfg.gamma, cfg.max_inner,
            cfg.chunk_rounds,
        )
        hit = _CHUNK_CACHE.get(chunk_key)
        if hit is None:
            hit = self._build_chunk()
            _CHUNK_CACHE[chunk_key] = hit
        self._chunk = hit
        self._repartition = self._build_repartition()

    # ------------------------------------------------------------------ #
    # state init
    # ------------------------------------------------------------------ #
    def init_state(
        self,
        f_nodes: Optional[np.ndarray] = None,
        h_nodes: Optional[np.ndarray] = None,
    ) -> EngineState:
        """Fresh sharded state in the *initial* bucket layout.

        ``f_nodes``/``h_nodes`` optionally seed the fluid and history
        from node-space vectors — the warm-start path
        (``SolverSession.warm_start`` re-seeds ``F = B' − (I−P)H`` and
        keeps the accumulated H, §2.2 residual identity).  Defaults
        reproduce the cold start ``F = B, H = 0``.
        """
        a, cfg = self.a, self.cfg
        dt = cfg.dtype
        put_row = lambda x: jax.device_put(x, self.row_sharding)
        put_rep = lambda x: jax.device_put(x, self.rep_sharding)
        f0 = a.f0 if f_nodes is None else self._to_slots(f_nodes)
        h0 = (np.zeros(a.f0.shape) if h_nodes is None
              else self._to_slots(h_nodes))
        fw = np.abs(f0) * a.w
        t0 = (fw.reshape(cfg.k, -1).max(axis=1) * 2.0 + 1e-30).astype(dt)
        self.w = put_row(a.w.astype(dt))
        self.src_slot = put_row(a.src_slot)
        self.dst_bucket = put_row(a.dst_bucket)
        self.dst_slot = put_row(a.dst_slot)
        self.wgt = put_row(a.wgt.astype(dt))
        if cfg.diffusion_backend == "bsr":
            self.tiles = put_row(np.asarray(a.tiles, dtype=dt))
            self.tile_dst = put_row(a.tile_dst)
            self.slot_out_deg = put_row(a.slot_out_deg)
        else:
            self.tiles = self.tile_dst = self.slot_out_deg = None
        return EngineState(
            f=put_row(f0.astype(dt)),
            h=put_row(h0.astype(dt)),
            outbox=put_row(
                np.zeros((cfg.k, a.n_rows * a.bucket_size), dtype=dt)
            ),
            t=put_row(t0),
            pos_of_bucket=put_rep(a.pos_of_bucket.astype(np.int32)),
            ops=put_row(np.zeros((cfg.k, 2), dtype=np.uint32)),
            rounds=put_rep(np.zeros((), dtype=np.int32)),
            armed=put_row(np.zeros((cfg.k, 2), dtype=np.uint32)),
        )

    def _to_slots(self, v_nodes: np.ndarray) -> np.ndarray:
        """Scatter a node-space [N] vector into the initial [R, S] layout."""
        a = self.a
        out = np.zeros(a.f0.shape, dtype=np.float64)
        valid = a.node_of_slot >= 0
        out[valid] = np.asarray(v_nodes, dtype=np.float64)[
            a.node_of_slot[valid]
        ]
        return out

    # ------------------------------------------------------------------ #
    # the jitted chunk: cfg.chunk_rounds × (adaptive local rounds + exchange)
    # ------------------------------------------------------------------ #
    def _build_chunk(self):
        cfg, a, axis = self.cfg, self.a, self.axis
        s = a.bucket_size
        r_total = a.n_rows
        b_loc = cfg.buckets_per_dev
        k = cfg.k
        use_bsr = cfg.diffusion_backend == "bsr"
        pallas_path = (jax.default_backend() == "tpu"
                       or cfg.pallas_interpret)

        def tile_push(tiles, tile_dst, sent, pos, row_armed):
            """BSR push: dense [S, S] tile matmuls instead of the per-edge
            scatter.  Returns the full-length [R*S] contribution in *row*
            space (current bucket positions).  ``row_armed`` marks the
            rows that send fluid: only their tiles are visited."""
            contrib_stable = _tile_push_stable(
                tiles, tile_dst, sent, r_total,
                use_pallas=pallas_path,
                interpret=cfg.pallas_interpret,
                buffer_depth=cfg.pallas_buffer_depth,
                row_armed=row_armed,
            )  # [R, S] indexed by stable bucket id
            # stable bucket space -> current row space via the position map
            inv = jnp.zeros(r_total, jnp.int32).at[pos].set(
                jnp.arange(r_total, dtype=jnp.int32)
            )
            return contrib_stable[inv].reshape(-1)

        def local_round(f, h, obox, t_d, ops_d, armed_d, pos, operands,
                        my_start, dang):
            """One frontier round on this device's [B_loc, S] rows.

            ``obox`` is the device's full-length [R*S] outbox; ``dang``
            is the [B_loc, S] dangling-slot mask (real node, zero real
            edges) charged one op per selected round — the §2.3
            accounting every other tier uses (edge pushes plus one per
            selected dangling node).  ``armed_d`` tallies the tiles of
            the rows with a selected node (bsr only).
            """
            w, src_slot, dst_bucket, dst_slot, wgt = operands[:5]
            with jax.named_scope("engine.select"):
                fw = jnp.abs(f) * w
                sel = fw > t_d  # [B_loc, S]
                any_sel = jnp.any(sel)
                sent = jnp.where(sel, f, jnp.zeros_like(f))
                h = h + sent
                f = f - sent
                t_d = jnp.where(any_sel, t_d, t_d / cfg.gamma)
                if use_bsr:
                    # same §2.3 count without the per-edge gather: every
                    # slot's real edges all fire when the slot is selected
                    dops = jnp.sum(jnp.where(sel, operands[7], 0))
                    row_armed = jnp.any(sel, axis=1)
                    armed_d = count_add(armed_d, operands[6].shape[1]
                                        * jnp.sum(row_armed,
                                                  dtype=jnp.int32))
                else:
                    row_idx = jnp.arange(f.shape[0])[:, None]
                    active_edges = sel[row_idx, src_slot] & (wgt != 0)
                    dops = jnp.sum(active_edges.astype(jnp.int32))
                dops = dops + jnp.sum((sel & dang).astype(jnp.int32))
                ops_d = count_add(ops_d, dops)
            with jax.named_scope("engine.push"):
                if use_bsr:
                    tiles, tile_dst = operands[5], operands[6]
                    contrib = tile_push(tiles, tile_dst, sent, pos,
                                        row_armed)
                else:
                    row_idx = jnp.arange(f.shape[0])[:, None]
                    msg = sent[row_idx, src_slot] * wgt  # [B_loc, E]
                    flat_dst = pos[dst_bucket] * s + dst_slot  # [B_loc, E]
                    contrib = jax.ops.segment_sum(
                        msg.reshape(-1), flat_dst.reshape(-1),
                        num_segments=r_total * s,
                    )
                mine = jax.lax.dynamic_slice(
                    contrib, (my_start,), (b_loc * s,)
                ).reshape(f.shape)
                f = f + mine
                contrib = jax.lax.dynamic_update_slice(
                    contrib, jnp.zeros(b_loc * s, contrib.dtype), (my_start,)
                )
                obox = obox + contrib
            return f, h, obox, t_d, ops_d, armed_d

        def chunk(f, h, outbox, t, pos, ops, rounds, armed, *operands):
            """shard_map body.  Per-device shards:

            f, h, w, src_slot, ...: [B_loc, S] / [B_loc, E]
            outbox: [1, R*S]   t: [1]   ops, armed: [1, 2]
            pos: [R] replicated
            operands: w, src_slot, dst_bucket, dst_slot, wgt
            [, tiles [B_loc, T, S, S], tile_dst [B_loc, T],
             slot_out_deg [B_loc, S] when bsr]
            """
            idx = jax.lax.axis_index(axis)
            my_start = idx * b_loc * s
            obox = outbox[0]
            t_d = t[0]
            ops_d = ops[0]
            armed_d = armed[0]
            # dangling-slot mask: real node (w != 0) with zero real edges.
            # Loop-invariant given the operands; the bsr path reads it off
            # the prebuilt per-slot degrees, the per-edge path rebuilds
            # them from the edge buffer (no operand-signature change).
            if use_bsr:
                slot_deg = operands[7]
            else:
                w_op, src_slot_op, wgt_op = (operands[0], operands[1],
                                             operands[4])
                row_idx = jnp.arange(w_op.shape[0])[:, None]
                slot_deg = jnp.zeros(w_op.shape, jnp.int32).at[
                    row_idx, src_slot_op
                ].add((wgt_op != 0).astype(jnp.int32))
            dang = (operands[0] != 0) & (slot_deg == 0)

            def body(carry):
                f, h, obox, t_d, ops_d, armed_d, i, fire = carry
                f, h, obox, t_d, ops_d, armed_d = local_round(
                    f, h, obox, t_d, ops_d, armed_d, pos, operands,
                    my_start, dang)
                with jax.named_scope("engine.residual"):
                    r_k = jnp.sum(jnp.abs(f))
                    s_k = jnp.sum(jnp.abs(obox))
                    fire_local = (s_k > r_k / 2.0).astype(jnp.int32)
                    fire = jax.lax.pmax(fire_local, axis)
                return f, h, obox, t_d, ops_d, armed_d, i + 1, fire

            def cond(carry):
                *_, i, fire = carry
                return (i < cfg.max_inner) & (fire == 0)

            f, h, obox, t_d, ops_d, armed_d, i, _fire = jax.lax.while_loop(
                cond, body,
                (f, h, obox, t_d, ops_d, armed_d, jnp.zeros((), jnp.int32),
                 jnp.zeros((), jnp.int32)),
            )
            # ---- fluid exchange: reduce-scatter outbox over devices ----
            with jax.named_scope("engine.exchange"):
                r_before = jnp.sum(jnp.abs(f))
                delta = jax.lax.psum_scatter(
                    obox.reshape(k, b_loc * s), axis, scatter_dimension=0,
                    tiled=False,
                ).reshape(f.shape)
                f = f + delta
                received = jnp.sum(jnp.abs(delta))
                t_new = jnp.where(
                    received > 0,
                    jnp.minimum(
                        jnp.where(
                            r_before > 0,
                            t_d * (r_before + received) / r_before,
                            received,
                        ),
                        received,
                    ),
                    t_d,
                )
                obox = jnp.zeros_like(obox)
            return (f, h, obox[None], t_new[None], pos, ops_d[None],
                    rounds + i, armed_d[None])

        n_operands = 8 if use_bsr else 5
        pr, pp = P(axis), P()
        mapped = shard_map(
            chunk,
            mesh=self.mesh,
            in_specs=(pr, pr, pr, pr, pp, pr, pp, pr) + (pr,) * n_operands,
            out_specs=(pr, pr, pr, pr, pp, pr, pp, pr),
            check_vma=False,
        )

        # the outputs keep the shardings ``init_state`` gives the inputs:
        # left to the compiler, a one-device axis hands ``t`` and ``ops``
        # back replicated, and the next call would trace again
        row = NamedSharding(self.mesh, pr)
        rep = NamedSharding(self.mesh, pp)
        out_shardings = (
            EngineState(f=row, h=row, outbox=row, t=row, pos_of_bucket=rep,
                        ops=row, rounds=rep, armed=row),
            {"r": rep, "s": rep, "residual": rep})

        @functools.partial(jax.jit, out_shardings=out_shardings)
        def run_chunk(state: EngineState, *operands):
            f, h, outbox, t, pos, ops, rounds, armed = (
                state.f, state.h, state.outbox, state.t,
                state.pos_of_bucket, state.ops, state.rounds, state.armed)
            for _ in range(cfg.chunk_rounds):
                f, h, outbox, t, pos, ops, rounds, armed = mapped(
                    f, h, outbox, t, pos, ops, rounds, armed, *operands)
            new = EngineState(f=f, h=h, outbox=outbox, t=t,
                              pos_of_bucket=pos, ops=ops, rounds=rounds,
                              armed=armed)
            with jax.named_scope("engine.residual"):
                stats = {
                    "r": jnp.sum(jnp.abs(f.reshape(cfg.k, -1)), axis=1),
                    "s": jnp.sum(jnp.abs(outbox), axis=1),
                    "residual": jnp.sum(jnp.abs(f)),
                }
            return new, stats

        return run_chunk

    # ------------------------------------------------------------------ #
    # in-graph bucket repartition (dynamic strategy / elastic scaling)
    # ------------------------------------------------------------------ #
    def _build_repartition(self):
        def run(state, row_perm, new_pos, operands):
            # _repart is the shared module-level jit (see _CHUNK_CACHE)
            new_state, arrs = _repart(state, row_perm, new_pos,
                                      tuple(operands))
            # keep row-sharded layout after the gather
            arrs = tuple(
                jax.device_put(x, self.row_sharding) for x in arrs
            )
            # and every field keeps init_state's sharding, so the next
            # chunk call finds its compiled program
            row = lambda x: jax.device_put(x, self.row_sharding)
            rep = lambda x: jax.device_put(x, self.rep_sharding)
            new_state = EngineState(
                f=row(new_state.f), h=row(new_state.h),
                outbox=row(new_state.outbox), t=row(new_state.t),
                pos_of_bucket=rep(new_state.pos_of_bucket),
                ops=row(new_state.ops), rounds=rep(new_state.rounds),
                armed=row(new_state.armed),
            )
            return new_state, arrs

        return run

    # ------------------------------------------------------------------ #
    # outer solve loop (host-driven controller, jitted chunks)
    # ------------------------------------------------------------------ #
    def solve(self, verbose: bool = False):
        cfg, a = self.cfg, self.a
        ex = BucketMoveExecutor(self, self.init_state())
        tol = cfg.target_error * cfg.eps
        history = []
        move_log = []
        n_moves = 0
        prev_ops = np.zeros(cfg.k, dtype=np.int64)
        resid = float("inf")
        chunk_i = -1
        for chunk_i in range(cfg.max_chunks):
            ex.state, stats = self._chunk(ex.state, *ex.chunk_operands())
            r = np.asarray(stats["r"])
            s_ = np.asarray(stats["s"])
            resid = float(np.asarray(stats["residual"])) + float(s_.sum())
            history.append(
                (int(np.asarray(ex.state.rounds)), resid, (r + s_).copy())
            )
            if verbose:
                print(f"chunk {chunk_i}: residual={resid:.3e} "
                      f"rounds={int(np.asarray(ex.state.rounds))}")
            if resid <= tol:
                break
            prev_ops = self.apply_control_plane(
                ex, r, s_, chunk_i, prev_ops, move_log)
        n_moves = len(move_log)
        x = self.extract_solution(ex.state, ex.row_of_bucket)
        ops = device_ops(ex.state.ops)
        return x, {
            "residual": resid,
            "chunks": chunk_i + 1,
            "rounds": int(np.asarray(ex.state.rounds)),
            "moves": n_moves,
            "move_log": move_log,
            "history": history,
            "converged": resid <= tol,
            "ops": ops,
            "n_edge_ops": int(ops.sum()),
        }

    def apply_control_plane(self, ex, r: np.ndarray, s_: np.ndarray,
                            step: int, prev_ops: np.ndarray,
                            move_log: list) -> np.ndarray:
        """One rebalancer pass on post-chunk stats (shared by ``solve``
        and the API session driver so the decision logic cannot
        diverge).  Builds the configured LoadSignal, applies every
        proposed MovePlan through ``ex``, appends executed moves to
        ``move_log`` as ``(step, src, dst, units)``, and returns the
        updated cumulative-ops baseline."""
        if self.rebalancer is None:
            return prev_ops
        sizes = ex.sizes()
        scale = (self.load_scale if self.load_scale is not None
                 else np.ones(self.cfg.k))
        if self.cfg.signal == "edge-ops":
            # the on-device counter is cumulative over the whole solve
            ops = device_ops(ex.state.ops)
            delta = ops - prev_ops
            sig = LoadSignal.from_edge_ops(delta * scale, sizes, step=step)
            prev_ops = ops
        else:
            sig = LoadSignal.from_residuals((r + s_) * scale, sizes,
                                            step=step)
        for plan in self.rebalancer.propose(sig):
            moved = ex.apply(plan)
            if moved:
                move_log.append((step, plan.src, plan.dst, moved))
        return prev_ops

    def gather_nodes(self, values, row_of_bucket: np.ndarray) -> np.ndarray:
        """Gather a bucket-space [R, S] state array back to node space:
        a bucket id's data lives at its *current* row while the node map
        indexes its *initial* row."""
        a = self.a
        v = np.asarray(values).reshape(a.n_rows, a.bucket_size)
        x = np.zeros(a.n, dtype=np.float64)
        for bid in range(a.n_rows):
            row0 = int(a.pos_of_bucket[bid])  # initial row (node map)
            row1 = int(row_of_bucket[bid])  # current row (data)
            nodes = a.node_of_slot[row0]
            valid = nodes >= 0
            if valid.any():
                x[nodes[valid]] = v[row1, valid]
        return x

    def extract_solution(self, state: EngineState,
                         row_of_bucket: np.ndarray) -> np.ndarray:
        """Gather H back to node space."""
        return self.gather_nodes(state.h, row_of_bucket)

    def _plan_move(self, row_of_bucket: np.ndarray, src_dev: int,
                   dst_dev: int, n_move: int, keep_min: int = 1
                   ) -> Tuple[Optional[np.ndarray], np.ndarray, int]:
        """Plan a row permutation moving up to ``n_move`` real buckets from
        ``src_dev`` to free (inert) rows on ``dst_dev``.

        ``keep_min`` is the floor of real buckets left on the source —
        1 for rebalancing moves (a PID never empties itself), 0 for the
        rescale drain (a dying device hands everything over).

        Returns ``(perm, new_row_of_bucket, moved)`` with
        ``perm[i] = old row whose contents land in new row i``
        (``jnp.take`` semantics).
        """
        cfg = self.cfg
        b_loc = cfg.buckets_per_dev
        n_real = cfg.k * (b_loc - cfg.headroom)
        dev_of_bucket = row_of_bucket // b_loc
        src_real = np.nonzero(dev_of_bucket[:n_real] == src_dev)[0]
        inert_ids = np.arange(n_real, row_of_bucket.shape[0])
        dst_free = inert_ids[dev_of_bucket[inert_ids] == dst_dev]
        moved = int(min(n_move, max(src_real.size - keep_min, 0),
                        dst_free.size))
        if moved == 0:
            return None, row_of_bucket, 0
        new_map = row_of_bucket.copy()
        perm = np.arange(row_of_bucket.shape[0], dtype=np.int32)
        for bid, q in zip(src_real[-moved:], dst_free[:moved]):
            p_row, q_row = int(new_map[bid]), int(new_map[q])
            perm[q_row], perm[p_row] = p_row, q_row
            new_map[bid], new_map[q] = q_row, p_row
        return perm, new_map, moved

    # ------------------------------------------------------------------ #
    # mid-solve PID rescale (elastic scale-up / device loss)
    # ------------------------------------------------------------------ #
    def _free_rows_per_device(self, row_of_bucket: np.ndarray) -> np.ndarray:
        """Inert (landing-capable) bucket rows currently on each device."""
        cfg = self.cfg
        n_real = cfg.k * (cfg.buckets_per_dev - cfg.headroom)
        dev_of_bucket = row_of_bucket // cfg.buckets_per_dev
        return np.bincount(dev_of_bucket[n_real:], minlength=cfg.k)

    def drain_for_shrink(self, ex, k_new: int):
        """Evacuate every real bucket owned by devices >= ``k_new`` onto
        the survivors' inert headroom rows, one bucket at a time to the
        survivor with the most free rows (deterministic, load-levelling).

        Runs through the existing :class:`~repro.balance.executors.
        BucketMoveExecutor` path — the same in-graph permutation the
        dynamic partition uses — so the drain IS a sequence of executed
        ``MovePlan``\\ s, returned as ``(src, dst, moved)`` triples.
        Raises when the surviving headroom cannot absorb the evacuation.
        """
        cfg = self.cfg
        sizes = ex.sizes()
        need = int(sizes[k_new:].sum())
        free = self._free_rows_per_device(ex.row_of_bucket)
        have = int(free[:k_new].sum())
        if need > have:
            raise ValueError(
                f"cannot shrink to k={k_new}: {need} real buckets must "
                f"evacuate but survivors have only {have} free headroom "
                f"rows (raise EngineConfig.headroom)"
            )
        drains = []
        for d in range(k_new, cfg.k):
            while ex.sizes()[d] > 0:
                free = self._free_rows_per_device(ex.row_of_bucket)
                free[k_new:] = -1  # dying devices never receive
                dst = int(np.argmax(free))
                moved = ex.apply(
                    MovePlan(src=d, dst=dst, units=1, kind="bucket"),
                    keep_min=0)
                assert moved == 1, (d, dst, moved)
                drains.append((d, dst, moved))
        return drains

    def rescale(self, ex, k_new: int, g, b: np.ndarray,
                buckets_per_dev: Optional[int] = None,
                strict: bool = False):
        """Grow/shrink the ``pid`` axis mid-solve without recomputing H.

        Shrink first *drains* the dying devices through the executor
        path (:meth:`drain_for_shrink` — headroom rows absorb the
        moves), so every byte of solver state leaves a lost device
        through the same collective permutation the rebalancer uses;
        then the axis is re-meshed at ``k_new`` over the store's cached
        engine-layout view and the fluid pair ``(F, H)`` is carried over
        in node space (the invariant ``B = (I−P)H + F`` travels with
        it).  Grow is the same re-mesh without a drain; the fresh
        layout is balanced by construction and the rebalancer spreads
        any residual skew.

        When the survivors' headroom cannot absorb the evacuation the
        drain is skipped and the state rides the node-space carry alone
        (``strict=True`` raises instead — tests that must exercise the
        executor drain use it).

        Returns ``(engine, executor, drains)`` — a NEW engine bound to
        ``k_new`` devices with a freshly seeded policy, its executor in
        the cold-start bucket layout of ``k_new`` (so a replay of the
        post-rescale move log over a cold start reproduces the
        ownership map exactly), and the executed drain triples.
        """
        cfg = self.cfg
        if k_new == cfg.k:
            return self, ex, []
        if k_new < 1:
            raise ValueError(f"k_new must be >= 1, got {k_new}")
        n_dev = len(jax.devices())
        if k_new > n_dev:
            raise ValueError(
                f"rescale to k={k_new} needs {k_new} physical devices, "
                f"have {n_dev}"
            )
        drains = []
        if k_new < cfg.k:
            need = int(ex.sizes()[k_new:].sum())
            have = int(self._free_rows_per_device(
                ex.row_of_bucket)[:k_new].sum())
            if need <= have or strict:
                drains = self.drain_for_shrink(ex, k_new)
        f_nodes = self.gather_nodes(ex.state.f, ex.row_of_bucket)
        h_nodes = self.gather_nodes(ex.state.h, ex.row_of_bucket)
        new_cfg = dataclasses.replace(
            cfg, k=k_new,
            buckets_per_dev=(buckets_per_dev if buckets_per_dev is not None
                             else cfg.buckets_per_dev))
        arrays = build_engine_arrays(g, b, new_cfg)
        engine = DistributedEngine(arrays, new_cfg, axis=self.axis)
        new_ex = BucketMoveExecutor(engine,
                                    engine.init_state(f_nodes, h_nodes))
        return engine, new_ex, drains
