"""Pod-scale GAME: entity-sharded random-effect banks with all-to-all
residual routing and cross-replica sharded updates.

The replicated path (game/random_effect.py) holds every random-effect
coordinate's [E, d] bank — plus its variances and tracker inputs — ON
EVERY device, so coefficient capacity is capped by one host no matter
how many devices the mesh has. Photon ML's headline claim is "hundreds
of billions of coefficients" (PAPER.md); that only works if memory AND
per-step work scale with the mesh. This module is that scaling story:

- **Hash placement** (:class:`EntityShardSpec`): entity ``e`` lives on
  shard ``e % n_shards`` at local bank row ``e // n_shards`` — the
  LongHashPartitioner analog, the SAME ownership rule as
  ``parallel.shuffle.entity_all_to_all``, and stable as E grows (new
  entities never re-home old ones, which the serving shard loader and
  incremental retraining both rely on).
- **Sharded banks** (:class:`ShardedREBank`): one ``[n * E_loc, d]``
  ``jax.Array`` sharded over the ``entity`` mesh axis; each device
  holds only its ``[E_loc, d]`` shard. Variance banks shard the same
  way, and the tracker never materializes anything [E]-sized — its
  stats are psum-reduced scalars.
- **Sharded updates** (:class:`PodRandomEffectProblem`): every bucket
  solve runs under ``shard_map`` — each replica computes ONLY its own
  entities' LBFGS/TRON/Newton steps against its local bank shard (the
  "Automatic Cross-Replica Sharding of Weight Update" recipe,
  PAPERS.md: replicas own disjoint slices of the update), with the CD
  objective's tracker reductions riding psum through the fused program.
- **Two-hop residual routing** (:class:`~photon_ml_tpu.game.
  residual_routing.PodResidualRouter`): per CD iteration ONE
  all_to_all carries each row's residual to its entity's owner shard,
  the owner scores/solves locally, and the reverse all_to_all carries
  the new scores back — two floats of traffic per row, zero host-side
  gathers (the tests count the ``overlap.device_get`` seam).

The streamed path (game/streaming.py) reuses the same fused sharded
segment solve: each ``SpilledREBuckets`` segment is split by the same
entity hash so a device only ever stages its own shard of a segment.

Weak-scaling contract (pinned by tests/test_pod_game.py): at N shards, per-device bank + optimizer-state bytes
are ~1/N of the replicated path for the same model, with CD parity
inside the established fp32 envelopes.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu import ownership
from photon_ml_tpu.game.model import RandomEffectModel
from photon_ml_tpu.game.random_effect import (
    LazyRandomEffectTracker,
    RandomEffectOptimizationProblem,
    RandomEffectTracker,
    gather_scores,
    score_block,
    score_kernel_name,
    scores_from_block,
)
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.game.residual_routing import PodResidualRouter
from photon_ml_tpu.obs.trace import span as obs_span
from photon_ml_tpu.optim.common import CONVERGENCE_REASON_NAMES
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

Array = jnp.ndarray

__all__ = [
    "EntityShardSpec",
    "ShardedREBank",
    "PodRandomEffectProblem",
    "PodRandomEffectModel",
    "entity_shard_of",
    "per_device_bytes",
]


def entity_shard_of(codes, num_shards: int):
    """The one placement rule, shared by training, streaming, the
    serving shard loader AND the scatter/gather router: entity code ->
    owning shard. Delegates to :mod:`photon_ml_tpu.ownership` so no
    plane can drift from the others."""
    return ownership.owner_of(np.asarray(codes), int(num_shards))


@dataclass(frozen=True)
class EntityShardSpec:
    """Static placement of an entity axis over ``num_shards`` devices."""

    num_shards: int
    num_entities: int

    @property
    def rows_per_shard(self) -> int:
        """Local bank rows per shard (>= 1 so empty banks stay valid)."""
        return ownership.rows_per_shard(self.num_entities, self.num_shards)

    @property
    def bank_rows(self) -> int:
        return self.num_shards * self.rows_per_shard

    def local_of(self, codes):
        return ownership.local_row_of(np.asarray(codes), self.num_shards)

    def sharded_row_of(self, codes):
        """Entity code -> row in the sharded [n * E_loc, d] layout."""
        return ownership.sharded_row_of(
            np.asarray(codes), self.num_shards, self.rows_per_shard
        )


def _mesh_key(mesh):
    return (
        tuple(mesh.axis_names),
        tuple(int(n) for n in mesh.devices.shape),
        tuple(d.id for d in mesh.devices.flat),
    )


def _entity_sharding(mesh):
    return NamedSharding(mesh, P(mesh.axis_names[0]))


# Zero-bank builders keyed by (mesh, rows, d): jit with out_shardings
# creates the sharded zeros ON DEVICE — no [E, d] host array is ever
# materialized, which is the whole point at pod scale.
_ZEROS_CACHE: dict = {}
# One shape-polymorphic replicate program per mesh (all-gather a sharded
# value to every device — model export / score hand-off, off hot path).
_REPL_CACHE: dict = {}
_POD_CACHE_MAX = 32


def _bounded_put(cache: dict, key, value):
    while len(cache) >= _POD_CACHE_MAX:
        cache.pop(next(iter(cache)))
    cache[key] = value
    return value


def _zeros_sharded(mesh, rows: int, d: int) -> Array:
    key = (_mesh_key(mesh), rows, d)
    fn = _ZEROS_CACHE.get(key)
    if fn is None:

        # (the functions handed to jax.jit name the XLA modules: pod_zeros,
        # pod_replicate, pod_update, pod_variance, pod_score,
        # pod_chunk_score here, pod_route_in / pod_route_out in the router)
        def pod_zeros(rows=rows, d=d):
            return jnp.zeros((rows, d), jnp.float32)

        fn = _bounded_put(
            _ZEROS_CACHE, key,
            # photon: sharding(axes=[entity], out=[entity])
            jax.jit(pod_zeros, out_shardings=_entity_sharding(mesh)),
        )
    return fn()


def _replicate(mesh, value: Array) -> Array:
    key = _mesh_key(mesh)
    fn = _REPL_CACHE.get(key)
    if fn is None:

        def pod_replicate(a):
            return a

        fn = _bounded_put(
            _REPL_CACHE, key,
            # photon: sharding(axes=[entity], in=[entity], out=[r])
            jax.jit(pod_replicate, out_shardings=NamedSharding(mesh, P())),
        )
    return fn(value)


class ShardedREBank:
    """One random-effect coefficient (or variance) bank, hash-sharded
    over the entity mesh axis. ``data`` is a [num_shards * E_loc, d]
    ``jax.Array`` with entity ``e`` at row
    ``(e % n) * E_loc + e // n`` — device ``s`` holds exactly the
    entities it owns, nothing else. Padding rows (local index beyond the
    shard's real entity count) are zeros and inert everywhere (the reg
    term sums them as 0, no solve ever touches them)."""

    __slots__ = ("mesh", "spec", "data")

    def __init__(self, mesh, spec: EntityShardSpec, data: Array):
        self.mesh = mesh
        self.spec = spec
        self.data = data

    @property
    def dim(self) -> int:
        return int(self.data.shape[1])

    @classmethod
    def zeros(cls, mesh, spec: EntityShardSpec, dim: int) -> "ShardedREBank":
        return cls(mesh, spec, _zeros_sharded(mesh, spec.bank_rows, dim))

    @classmethod
    def from_global(cls, mesh, spec: EntityShardSpec, bank) -> "ShardedREBank":
        """[E, d] entity-code-ordered bank -> sharded layout. The gather
        runs on device; only the device_put re-shard moves data."""
        bank = jnp.asarray(bank, jnp.float32)
        rows = np.arange(spec.bank_rows, dtype=np.int64)
        e = (rows % spec.rows_per_shard) * spec.num_shards + (
            rows // spec.rows_per_shard
        )
        valid = e < spec.num_entities
        safe = np.minimum(e, max(spec.num_entities - 1, 0))
        gathered = jnp.take(bank, jnp.asarray(safe, jnp.int32), axis=0)
        gathered = jnp.where(jnp.asarray(valid)[:, None], gathered, 0.0)
        return cls(
            mesh, spec, jax.device_put(gathered, _entity_sharding(mesh))
        )

    def to_global(self) -> Array:
        """Sharded layout -> replicated [E, d] in entity-code order (a
        device-side all-gather; model export / parity checks only — the
        CD hot path never calls this)."""
        rows = self.spec.sharded_row_of(
            np.arange(self.spec.num_entities, dtype=np.int64)
        )
        out = jnp.take(self.data, jnp.asarray(rows, jnp.int32), axis=0)
        return _replicate(self.mesh, out)

    # photon: sharding(export)
    def __array__(self, dtype=None):
        # host materialization is an explicit, counted readback
        host = overlap.device_get(self.to_global())
        return np.asarray(host, dtype) if dtype is not None else np.asarray(host)

    def per_device_bytes(self) -> int:
        return per_device_bytes(self.data)


def per_device_bytes(*values) -> int:
    """Max bytes any single device holds across the given arrays /
    ShardedREBanks — the weak-scaling accounting the tests pin
    (per-device bank + optimizer-state bytes ~flat as total
    coefficients grow with the shard count)."""
    per: Dict[object, int] = {}
    for v in values:
        arr = v.data if isinstance(v, ShardedREBank) else v
        for s in arr.addressable_shards:
            per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)
    return max(per.values()) if per else 0


# ---------------------------------------------------------------------------
# sharded fused programs
# ---------------------------------------------------------------------------
#
# One program object per (mesh, solver kind) — jit re-specializes per
# block shape internally, so every capacity class reuses the same
# wrapper. Gather + solve + scatter + the psum'd tracker reductions run
# in ONE dispatch per class block, mirroring the replicated path's
# _fused programs; no [E]-sized value ever leaves its shard.

_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 64

_N_REASONS = max(CONVERGENCE_REASON_NAMES) + 1


def _cached_program(key, build):
    from photon_ml_tpu.utils.memo import get_or_build

    return get_or_build(_PROGRAM_CACHE, _PROGRAM_CACHE_MAX, key, build)


def _donate_args():
    from photon_ml_tpu.utils.backend import effective_platform

    return (0,) if effective_platform() != "cpu" else ()


def _build_update_program(solvers, kind: str, mesh, axis: str,
                          with_slots: bool = True):
    """Sharded fused bucket update: each shard gathers ITS entities'
    bank rows, folds the residual into per-sample offsets, runs the
    vmapped per-entity solver on its slice only, scatters the new rows
    back into its local bank shard, and psums the tracker scalars. The
    bank shard is donated off-CPU (in-place scatter, like the
    replicated fused programs).

    ``with_slots``: offsets arrive as routed slot buffers + a static
    slot index per sample (the in-memory two-hop path); False takes a
    direct per-sample offsets block (the streamed-segment path, whose
    residual fold is host-side by the out-of-core contract)."""
    core = getattr(solvers, kind)
    ax = axis
    off_spec = (P(ax), P(ax)) if with_slots else (P(ax),)

    # photon: sharding(axes=[entity], in=?, out=[entity,r,r,r], donates=[0])
    @partial(jax.jit, donate_argnums=_donate_args())
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(ax), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax),
        ) + off_spec + (P(), P()),
        out_specs=(P(ax), P(), P(), P()),
        check_vma=False,
    )
    def pod_update(bank_l, lrow, valid, ix, v, lab, w, *rest):
        if with_slots:
            offslot, slots, l1, l2 = rest
            off = jnp.where(
                offslot >= 0, jnp.take(slots, jnp.maximum(offslot, 0)), 0.0
            )
        else:
            off, l1, l2 = rest
        e_loc = bank_l.shape[0]
        safe = jnp.minimum(lrow, e_loc - 1)
        sl = jnp.where(valid[:, None], jnp.take(bank_l, safe, axis=0), 0.0)
        new_sl, iters, reasons = core(sl, ix, v, lab, off, w, l1, l2)
        idx = jnp.where(valid, lrow, e_loc)  # pad lanes drop out of bounds
        bank_l = bank_l.at[idx].set(new_sl, mode="drop")
        vi = jnp.where(valid, iters, 0)
        it_sum = lax.psum(jnp.sum(vi), ax)
        it_max = lax.pmax(jnp.max(vi), ax)
        r = jnp.where(valid, reasons, _N_REASONS)  # pad lanes -> extra bin
        counts = lax.psum(
            jnp.bincount(r, length=_N_REASONS + 1)[:_N_REASONS], ax
        )
        return bank_l, it_sum, it_max, counts

    return pod_update


def _build_variance_program(solvers, mesh, axis: str,
                            with_slots: bool = True):
    """Sharded Hdiag pass at the just-solved rows, writing a sharded
    variance bank — the computeVariances analog with no replicated
    [E, d] anywhere."""
    from photon_ml_tpu.optim.problem import _VARIANCE_EPSILON

    hdiag = solvers.hdiag
    ax = axis
    off_spec = (P(ax), P(ax)) if with_slots else (P(ax),)

    # photon: sharding(axes=[entity], in=?, out=[entity], donates=[0])
    @partial(jax.jit, donate_argnums=_donate_args())
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(ax), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax),
        ) + off_spec + (P(),),
        out_specs=P(ax),
        check_vma=False,
    )
    def pod_variance(var_l, bank_l, lrow, valid, ix, v, lab, w, *rest):
        if with_slots:
            offslot, slots, l2 = rest
            off = jnp.where(
                offslot >= 0, jnp.take(slots, jnp.maximum(offslot, 0)), 0.0
            )
        else:
            off, l2 = rest
        e_loc = bank_l.shape[0]
        safe = jnp.minimum(lrow, e_loc - 1)
        sl = jnp.where(valid[:, None], jnp.take(bank_l, safe, axis=0), 0.0)
        hd = hdiag(sl, ix, v, lab, off, w, l2)
        idx = jnp.where(valid, lrow, e_loc)
        return var_l.at[idx].set(
            1.0 / (hd + _VARIANCE_EPSILON), mode="drop"
        )

    return pod_variance


def _build_chunk_score_program(mesh, axis: str, n_dev: int):
    """Streamed-chunk scoring against a sharded bank: chunk columns are
    replicated (they were just uploaded from a host chunk — out-of-core
    data has no resident device home), each shard scores only the rows
    it OWNS, and one psum assembles the row vector. Traffic is O(R) per
    chunk — never a bank gather, never a host crossing."""
    ax = axis

    # photon: sharding(axes=[entity], in=[entity,r,r,r,r], out=[r])
    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(ax), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def pod_chunk_score(bank_l, codes, ix, v, valid):
        e_loc = bank_l.shape[0]
        me = lax.axis_index(ax)
        mine = valid & (ownership.owner_of(codes, n_dev) == me)
        lrow = jnp.minimum(
            ownership.local_row_of(jnp.maximum(codes, 0), n_dev), e_loc - 1
        )
        w_rows = jnp.take(bank_l, jnp.where(mine, lrow, 0), axis=0)
        s = jnp.sum(v * jnp.take_along_axis(w_rows, ix, axis=1), axis=-1)
        return lax.psum(jnp.where(mine, s, 0.0), ax)

    return pod_chunk_score


def _build_score_program(mesh, axis: str, n_dev: int, cap: int, identity):
    """Hop 2 of the residual exchange, fused with the local scoring:
    each owner shard scores its received row slots against its LOCAL
    bank rows, then the reverse all_to_all lands each score back at the
    row that sent the residual — one dispatch, one collective, zero
    host crossings. The slots a dense solver block holds are scored
    from that block (``blocks``: ``(lrow, ix, v, offslot)`` of each
    ``_PodBlock``, what ``pod_update`` runs on; ``identity`` says which
    carry the tiled arange) by ``random_effect.score_block`` and placed
    by ``offslot``; ``rest`` (``(slots, lrow, valid, ix, v)``: a
    compacted slot list, or every slot where no block scores) keeps the
    element gather."""
    ax = axis
    n_slots = n_dev * cap

    # photon: sharding(axes=[entity], in=[entity,*], out=[entity])
    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax)),
        out_specs=P(ax),
        check_vma=False,
    )
    def pod_score(bank_l, blocks, rest, send_pos):
        e_loc = bank_l.shape[0]
        s = jnp.zeros((n_slots,), jnp.float32)
        for (lrow, ix, v, offslot), ident in zip(blocks, identity):
            # (a padding lane's row lies past the shard: zeros, on no slot)
            w = jnp.take(bank_l, lrow, axis=0, mode="fill", fill_value=0)
            at = jnp.where(offslot >= 0, offslot, n_slots)
            s = s.at[at.reshape(-1)].set(
                score_block(w, ix, v, ident).reshape(-1), mode="drop"
            )
        if rest is not None:
            slots, lrow, valid, ix, v = rest
            g = gather_scores(bank_l, jnp.minimum(lrow, e_loc - 1), ix, v)
            if slots is None:
                s = jnp.where(valid, g, 0.0)
            else:
                s = s.at[slots].set(g, mode="drop")
        blocks_out = s.reshape(n_dev, cap)
        back = lax.all_to_all(
            blocks_out, ax, split_axis=0, concat_axis=0, tiled=False
        ).reshape(-1)
        safe_p = jnp.minimum(send_pos, n_slots - 1)
        return jnp.where(send_pos < n_slots, back[safe_p], 0.0)

    return pod_score


# ---------------------------------------------------------------------------
# pod view of a RandomEffectDataset
# ---------------------------------------------------------------------------


def _owner_positions(codes: np.ndarray, spec: EntityShardSpec):
    """Where each entity of a block goes: (owner shard, local bank row,
    rank among the block's entities of the same owner, the fullest
    owner's count, at least 1)."""
    sh = entity_shard_of(codes, spec.num_shards)
    counts = np.bincount(sh, minlength=spec.num_shards)
    order = np.argsort(sh, kind="stable")
    pos = np.empty(len(codes), np.int64)
    pos[order] = np.arange(len(codes)) - np.repeat(
        np.cumsum(counts) - counts, counts
    )
    return sh, spec.local_of(codes), pos, max(1, int(counts.max()))


@dataclass
class _PodBlock:
    """One solver block: a capacity class split by entity hash into
    per-shard padded blocks [n_dev * E_blk, S(, k)] (leading dim sharded),
    or, where a shard's share of the class is over ``dense_bytes_budget``,
    one of its ``sub_blocks`` equal sub-blocks, which share one compiled
    program. ``kind`` is decided from the block a DEVICE holds
    (``RandomEffectOptimizationProblem.dense_block_plan``): a class too
    large for the dense solvers as one replicated bucket still runs them
    on each device's share."""

    kind: str
    num_real: int  # real entities across all shards (tracker accounting)
    sub_blocks: int  # how many blocks its capacity class was split into
    identity: bool  # indices are the tiled arange (k == local_dim)
    lrow: Array
    valid: Array
    ix: Array
    v: Array
    lab: Array
    w: Array
    offslot: Array


class _PodView:
    """Device-resident, entity-hash-sharded view of one
    RandomEffectDataset: the residual router, the per-owner scoring
    slots, and the per-capacity-class solver blocks. Built host-side
    ONCE per (dataset, mesh) and reused every CD iteration — only the
    residual values move after that."""

    def __init__(self, mesh, dataset: RandomEffectDataset, base_problem,
                 axis: Optional[str] = None):
        self.mesh = mesh
        # default: 1-D pod mesh. The unified (grid, entity) mesh passes
        # axis explicitly — row currency and blocks shard over the
        # entity axis and replicate over the grid axis, so this same
        # view (and the router's static tables) serves every λ member.
        axis = axis or mesh.axis_names[0]
        self.axis = axis
        n_dev = int(mesh.shape[axis])
        self.n_dev = n_dev
        self.num_rows = int(dataset.row_entity_codes.shape[0])
        self.spec = EntityShardSpec(n_dev, dataset.num_entities)
        self.blocks: List[_PodBlock] = []
        with obs_span("pod.view_build", shards=n_dev) as build_span:
            self._build(mesh, dataset, base_problem)
            build_span.set(
                blocks=len(dataset.buckets), sub_blocks=len(self.blocks),
                slots=self.router.num_slots,
            )

    def _build(self, mesh, dataset: RandomEffectDataset, base_problem):
        axis, n_dev = self.axis, self.n_dev
        e_loc = self.spec.rows_per_shard
        sharding = NamedSharding(mesh, P(axis))

        def put(a):
            # host array -> its shards, each straight to its device (never
            # the whole array on the default device first)
            return jax.device_put(a, sharding)

        codes = np.asarray(dataset.row_entity_codes, np.int64)
        self.router = PodResidualRouter(mesh, codes, axis=axis)
        cap = self.router.cap

        # -- scoring slots: every valid row's features staged at its
        # owner's (source, rank) slot — covers active AND passive rows,
        # exactly like score_random_effect on the replicated path
        slot_row = self.router.slot_row  # [owner, slot] -> gid
        flat_gid = slot_row.reshape(-1)
        s_valid = flat_gid >= 0
        safe_gid = np.maximum(flat_gid, 0)
        slot_ix = np.where(
            s_valid[:, None], dataset.row_local_indices[safe_gid], 0
        ).astype(np.int32)
        slot_v = np.where(
            s_valid[:, None], dataset.row_local_values[safe_gid], 0.0
        ).astype(np.float32)
        slot_codes = np.where(s_valid, codes[safe_gid], 0)
        slot_lrow = np.where(
            s_valid, self.spec.local_of(slot_codes), e_loc
        ).astype(np.int32)
        self.slot_ix = put(slot_ix)
        self.slot_v = put(slot_v)
        self.slot_lrow = put(slot_lrow)
        self.slot_valid = put(s_valid)

        # -- solver blocks: each bucket's entities split by hash; every
        # sample's residual offset arrives via its row's scoring slot
        # (same owner device by construction: a sample's entity IS the
        # slot's owner), so the solve needs no second exchange
        slot_of_row = self.router.slot_of_row
        d_local = dataset.local_dim
        # [owner, slot]: the slots no dense block holds, left to the gather
        unscored = slot_row >= 0
        for bucket in dataset.buckets:
            b_codes = np.asarray(bucket.entity_codes, np.int64)
            sh, lo, pos, e_blk = _owner_positions(b_codes, self.spec)
            S = bucket.capacity
            kk = bucket.indices.shape[2]
            # the block a device holds decides the solver, and how many
            # equal sub-blocks keep each dense program under the budget
            kind, e_cap = base_problem.dense_block_plan(
                e_blk, S, d_local, bucket.identity_indices
            )
            n_sub = -(-e_blk // e_cap)
            e_sub = -(-e_blk // n_sub)
            gids = bucket.row_index
            offslot = np.where(
                gids >= 0, slot_of_row[np.maximum(gids, 0)], -1
            ).astype(np.int32)
            rows_total = n_dev * e_sub
            if scores_from_block(kind, d_local):
                # scored from the block: its slots leave the gather's list
                held = offslot >= 0
                unscored[
                    np.broadcast_to(sh[:, None], held.shape)[held],
                    offslot[held],
                ] = False
            for j in range(n_sub):
                m = (pos // e_sub) == j
                dest = sh[m] * e_sub + pos[m] % e_sub
                b_lrow = np.full(rows_total, e_loc, np.int32)
                b_valid = np.zeros(rows_total, bool)
                b_ix = np.zeros((rows_total, S, kk), np.int32)
                b_v = np.zeros((rows_total, S, kk), np.float32)
                b_lab = np.zeros((rows_total, S), np.float32)
                b_w = np.zeros((rows_total, S), np.float32)
                b_offslot = np.full((rows_total, S), -1, np.int32)
                b_lrow[dest] = lo[m]
                b_valid[dest] = True
                b_ix[dest] = bucket.indices[m]
                b_v[dest] = bucket.values[m]
                b_lab[dest] = bucket.labels[m]
                b_w[dest] = bucket.weights[m]
                b_offslot[dest] = offslot[m]
                self.blocks.append(_PodBlock(
                    kind=kind,
                    num_real=int(m.sum()),
                    sub_blocks=n_sub,
                    identity=bucket.identity_indices,
                    lrow=put(b_lrow),
                    valid=put(b_valid),
                    ix=put(b_ix),
                    v=put(b_v),
                    lab=put(b_lab),
                    w=put(b_w),
                    offslot=put(b_offslot),
                ))

        # -- scoring: the slots a DENSE block holds are scored from it
        # (random_effect.score_plan's rule, on a device's share); passive
        # rows and a sparse block's keep the gather, over a compacted
        # slot list a device (padded to the fullest device's)
        scored = [
            b for b in self.blocks if scores_from_block(b.kind, d_local)
        ]
        self.score_gather_rows = int(unscored.sum())
        self.score_block_rows = int(s_valid.sum()) - self.score_gather_rows
        self._score_blocks = tuple(
            (b.lrow, b.ix, b.v, b.offslot) for b in scored
        )
        if not scored:
            self._score_rest = (
                None, self.slot_lrow, self.slot_valid, self.slot_ix,
                self.slot_v,
            )
        elif self.score_gather_rows:
            n_slots = self.router.num_slots
            width = int(unscored.sum(axis=1).max())
            # a device's unscored slots first, in slot order
            order = np.argsort(~unscored, axis=1, kind="stable")[:, :width]
            live = np.take_along_axis(unscored, order, axis=1).reshape(-1)
            flat = (order + np.arange(n_dev)[:, None] * n_slots).reshape(-1)
            self._score_rest = (
                put(np.where(live, order.reshape(-1), n_slots).astype(np.int32)),
                put(np.where(live, slot_lrow[flat], e_loc).astype(np.int32)),
                None,
                put(np.where(live[:, None], slot_ix[flat], 0)),
                put(np.where(live[:, None], slot_v[flat], 0.0)),
            )
        else:
            self._score_rest = None
        identity = tuple(b.identity for b in scored)
        self._score = _cached_program(
            ("score", _mesh_key(mesh), n_dev, cap, identity),
            lambda: _build_score_program(mesh, axis, n_dev, cap, identity),
        )

    @property
    def score_kernel(self) -> str:
        return score_kernel_name(
            self.score_block_rows, self.score_gather_rows
        )

    def entities_by_kind(self) -> Dict[str, int]:
        """Real entities a bank update solves, by solver kind."""
        out: Dict[str, int] = {}
        for b in self.blocks:
            out[b.kind] = out.get(b.kind, 0) + b.num_real
        return out

    def per_device_data_bytes(self) -> int:
        """Per-device bytes of the staged solver blocks + scoring slots
        (the dataset side of the weak-scaling accounting)."""
        arrays = [self.slot_ix, self.slot_v, self.slot_lrow, self.slot_valid]
        for b in self.blocks:
            arrays += [b.lrow, b.valid, b.ix, b.v, b.lab, b.w, b.offslot]
        return per_device_bytes(*arrays)


# ---------------------------------------------------------------------------
# the sharded problem
# ---------------------------------------------------------------------------


class PodRandomEffectProblem:
    """Entity-sharded twin of RandomEffectOptimizationProblem: same
    solver cores, same convergence semantics, but the bank / variances /
    tracker inputs / per-entity data all live sharded over the entity
    mesh axis, residuals arrive via one all_to_all, and every update is
    a cross-replica sharded step (each replica solves only the entities
    it owns).

    ``base`` must carry ``mesh=None`` — the pod layer owns placement;
    the base problem contributes solver construction, solver-kind
    selection and regularization semantics.
    """

    def __init__(self, base: RandomEffectOptimizationProblem, mesh):
        if base.mesh is not None:
            raise ValueError(
                "PodRandomEffectProblem wraps a mesh-less base problem; "
                "the entity mesh is owned by the pod layer"
            )
        self.base = base
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        if self.axis != ENTITY_AXIS:
            raise ValueError(
                f"pod mesh must carry the {ENTITY_AXIS!r} axis, got "
                f"{mesh.axis_names!r}"
            )
        self.num_shards = int(mesh.shape[self.axis])
        self._views: Dict[int, tuple] = {}

    def spec_for(self, dataset: RandomEffectDataset) -> EntityShardSpec:
        return EntityShardSpec(self.num_shards, dataset.num_entities)

    def init_bank(self, dataset: RandomEffectDataset) -> ShardedREBank:
        return ShardedREBank.zeros(
            self.mesh, self.spec_for(dataset), dataset.local_dim
        )

    def pod_view(self, dataset: RandomEffectDataset) -> _PodView:  # photon: entropy(id-keyed device-view memo; weakref-pinned, never serialized)
        """The sharded device view, built once per dataset (weakref-keyed
        like the base problem's device caches)."""
        key = id(dataset)
        hit = self._views.get(key)
        if hit is not None and hit[0]() is dataset:
            return hit[1]
        view = _PodView(self.mesh, dataset, self.base)
        cache = self._views
        ref = weakref.ref(dataset, lambda _, k=key, c=cache: c.pop(k, None))
        cache[key] = (ref, view)
        return view

    def prepare(self, dataset: RandomEffectDataset) -> None:
        """Stage the pod view (routing tables, sharded blocks, scoring
        slots) — the overlap prefetched-dispatch hook."""
        self.pod_view(dataset)

    def _coerce_bank(self, bank, dataset) -> ShardedREBank:
        if isinstance(bank, ShardedREBank):
            return bank
        # replicated [E, d] (warm start / checkpoint restore): shard it
        return ShardedREBank.from_global(
            self.mesh, self.spec_for(dataset), bank
        )

    def update_bank(
        self,
        bank,
        dataset: RandomEffectDataset,
        residual_offsets: Optional[Array] = None,
        with_variances: bool = False,
        defer_tracker: bool = False,
    ):
        """One cross-replica sharded bank update. ``residual_offsets``
        is the row-aligned [n] offsets-plus-residual vector (the CD loop
        always has it in hand); the pod path routes it device-side —
        there is no stored-offsets fallback because the routed slots ARE
        the offset currency here."""
        if residual_offsets is None:
            raise ValueError(
                "the pod update requires the row-aligned residual/offsets "
                "vector; pass dataset offsets (+ residual) like the CD "
                "loop does"
            )
        view = self.pod_view(dataset)
        bank = self._coerce_bank(bank, dataset)
        l1, l2 = self.base.regularization.split(self.base.reg_weight)
        l1_d, l2_d = jnp.float32(l1), jnp.float32(l2)
        with obs_span("pod.route_in"):
            slots = view.router.route_in(residual_offsets)  # hop 1
        solvers = self.base._solvers
        data = bank.data
        if _donate_args():
            # one defensive copy so the fused updates can DONATE the
            # bank shards while the caller's reference stays valid
            # (same contract as the replicated fused path)
            data = jnp.array(data, copy=True)
        n_reals: List[int] = []
        stat_vecs: List[Array] = []
        var_data = None
        if with_variances:
            var_data = _zeros_sharded(
                self.mesh, bank.spec.bank_rows, bank.dim
            )
        for blk in view.blocks:
            # (a class's sub-blocks share one program and hand the donated
            # bank from one to the next)
            fused = _cached_program(
                ("update", _mesh_key(self.mesh), blk.kind, True),
                lambda kind=blk.kind: _build_update_program(
                    solvers, kind, self.mesh, self.axis, with_slots=True
                ),
            )
            with obs_span(
                "pod.update", kind=blk.kind, sub_blocks=blk.sub_blocks,
                entities=blk.num_real,
            ):
                data, it_sum, it_max, counts = fused(
                    data, blk.lrow, blk.valid, blk.ix, blk.v, blk.lab, blk.w,
                    blk.offslot, slots, l1_d, l2_d,
                )
            if with_variances:
                fused_var = _cached_program(
                    ("variance", _mesh_key(self.mesh), True),
                    lambda: _build_variance_program(
                        solvers, self.mesh, self.axis, with_slots=True
                    ),
                )
                var_data = fused_var(
                    var_data, data, blk.lrow, blk.valid, blk.ix, blk.v,
                    blk.lab, blk.w, blk.offslot, slots, l2_d,
                )
            n_reals.append(blk.num_real)
            stat_vecs.append(
                jnp.concatenate([jnp.stack([it_sum, it_max]), counts])
            )
        new_bank = ShardedREBank(self.mesh, bank.spec, data)
        if stat_vecs:
            total = sum(n_reals)

            def _finalize(all_stats, total=total):
                iter_sum = int(all_stats[:, 0].sum())
                iter_max = int(all_stats[:, 1].max())
                count_vec = all_stats[:, 2:].sum(axis=0)
                counts_dict: Dict[str, int] = {
                    CONVERGENCE_REASON_NAMES.get(code, "?"): int(cnt)
                    for code, cnt in enumerate(count_vec)
                    if cnt
                }
                return RandomEffectTracker(
                    num_entities=total,
                    iterations_mean=iter_sum / total,
                    iterations_max=iter_max,
                    reason_counts=counts_dict,
                )

            deferred = overlap.Deferred(jnp.stack(stat_vecs), _finalize)
            if defer_tracker and not deferred.done:
                tracker = LazyRandomEffectTracker(deferred)
            else:
                tracker = deferred.result()
        else:
            tracker = RandomEffectTracker(0, 0.0, 0, {})
        if with_variances:
            return new_bank, tracker, ShardedREBank(
                self.mesh, bank.spec, var_data
            )
        return new_bank, tracker

    def update_segment(
        self,
        bank: ShardedREBank,
        entity_codes: np.ndarray,
        arrays: Dict[str, np.ndarray],
        offsets: np.ndarray,
        *,
        kind: str,
    ):
        """Sharded update of ONE streamed bucket segment
        (game/streaming.SpilledREBuckets): the segment's entities are
        split by the entity hash and each device stages/solves only its
        shard of the segment — the "each host stages only its shard's
        segments" contract at device granularity. Residual offsets are
        already folded host-side (the out-of-core path's score stores
        live on disk), so this uses the direct-offset program variant.
        Returns (new bank, tracker-stat vec Deferred payload) shaped
        like the in-memory path's per-block stats."""
        n_dev = self.num_shards
        spec = bank.spec
        e_loc = spec.rows_per_shard
        sharding = _entity_sharding(self.mesh)
        codes = np.asarray(entity_codes, np.int64)
        sh, lo, pos, e_blk = _owner_positions(codes, spec)
        dest = sh * e_blk + pos
        rows_total = n_dev * e_blk
        S = arrays["lab"].shape[1]
        kk = arrays["ix"].shape[2]
        b_lrow = np.full(rows_total, e_loc, np.int32)
        b_valid = np.zeros(rows_total, bool)
        b_ix = np.zeros((rows_total, S, kk), np.int32)
        b_v = np.zeros((rows_total, S, kk), np.float32)
        b_lab = np.zeros((rows_total, S), np.float32)
        b_w = np.zeros((rows_total, S), np.float32)
        b_off = np.zeros((rows_total, S), np.float32)
        b_lrow[dest] = lo
        b_valid[dest] = True
        b_ix[dest] = arrays["ix"]
        b_v[dest] = arrays["v"]
        b_lab[dest] = arrays["lab"]
        b_w[dest] = arrays["wgt"]
        b_off[dest] = np.asarray(offsets, np.float32)
        put = partial(jax.device_put, device=sharding)
        l1, l2 = self.base.regularization.split(self.base.reg_weight)
        fused = _cached_program(
            ("update", _mesh_key(self.mesh), kind, False),
            lambda: _build_update_program(
                solvers=self.base._solvers, kind=kind, mesh=self.mesh,
                axis=self.axis, with_slots=False,
            ),
        )
        data, it_sum, it_max, counts_v = fused(
            bank.data,
            put(jnp.asarray(b_lrow)), put(jnp.asarray(b_valid)),
            put(jnp.asarray(b_ix)), put(jnp.asarray(b_v)),
            put(jnp.asarray(b_lab)), put(jnp.asarray(b_w)),
            put(jnp.asarray(b_off)),
            jnp.float32(l1), jnp.float32(l2),
        )
        stat_vec = jnp.concatenate(
            [jnp.stack([it_sum, it_max]), counts_v]
        )
        return ShardedREBank(self.mesh, spec, data), stat_vec

    def segment_tracker(self, stat_vecs, num_entities: int,
                        defer: bool = True):
        """Fold per-segment stat vecs into one RandomEffectTracker —
        deferred so the CD loop's single batched readback fetches it."""

        def _finalize(all_stats, total=max(num_entities, 1)):
            iter_sum = int(all_stats[:, 0].sum())
            iter_max = int(all_stats[:, 1].max())
            count_vec = all_stats[:, 2:].sum(axis=0)
            counts_dict: Dict[str, int] = {
                CONVERGENCE_REASON_NAMES.get(code, "?"): int(cnt)
                for code, cnt in enumerate(count_vec)
                if cnt
            }
            return RandomEffectTracker(
                num_entities=num_entities,
                iterations_mean=iter_sum / total,
                iterations_max=iter_max,
                reason_counts=counts_dict,
            )

        deferred = overlap.Deferred(jnp.stack(list(stat_vecs)), _finalize)
        if defer and not deferred.done:
            return LazyRandomEffectTracker(deferred)
        return deferred.result()

    def score_chunk(self, bank: ShardedREBank, codes, ix, v, valid) -> Array:
        """[R] scores of one streamed chunk against the sharded bank:
        each shard scores its OWN rows, psum assembles — the bank never
        replicates, the chunk columns ride the upload they already pay
        on the replicated streaming path."""
        fn = _cached_program(
            ("chunk_score", _mesh_key(self.mesh)),
            lambda: _build_chunk_score_program(
                self.mesh, self.axis, self.num_shards
            ),
        )
        return fn(
            bank.data, jnp.asarray(codes), jnp.asarray(ix),
            jnp.asarray(v), jnp.asarray(valid),
        )

    def score(self, bank, dataset: RandomEffectDataset) -> Array:
        """Row-aligned [n] scores via the fused hop-2 program: owners
        score their slots locally, the reverse all_to_all returns each
        score to its row. Output is replicated (the CD score algebra's
        currency) — an O(n) row vector, never anything [E]-sized."""
        view = self.pod_view(dataset)
        bank = self._coerce_bank(bank, dataset)
        with obs_span("pod.score"):
            rows = view._score(
                bank.data, view._score_blocks, view._score_rest,
                view.router._send_pos,
            )
        with obs_span("pod.replicate"):
            return _replicate(self.mesh, rows)[: view.num_rows]

    def regularization_term_device(self, bank) -> Array:
        """Reg term over the SHARDED bank — the sum reduces device-side
        (padding rows are zeros and contribute nothing); the scalar
        joins the CD iteration's one batched readback."""
        data = bank.data if isinstance(bank, ShardedREBank) else bank
        l1, l2 = self.base.regularization.split(self.base.reg_weight)
        term = 0.5 * l2 * jnp.sum(data * data)
        if l1:
            term = term + l1 * jnp.sum(jnp.abs(data))
        return term

    def regularization_term(self, bank) -> float:
        return float(overlap.device_get(self.regularization_term_device(bank)))


class PodRandomEffectModel(RandomEffectModel):
    """RandomEffectModel whose bank lives SHARDED: ``bank`` /
    ``variances`` materialize a replicated view lazily (export,
    validation scoring — off the CD hot path), while the pod coordinate
    trains and scores against ``sharded_bank`` directly. Subclassing
    keeps every isinstance-dispatched consumer (model_io.save, the
    drivers' validation scorer) working unchanged."""

    # not a @dataclass: bank/variances are lazy properties over the
    # sharded state instead of stored fields
    def __init__(
        self,
        sharded_bank: ShardedREBank,
        re_dataset: RandomEffectDataset,
        random_effect_type: str,
        feature_shard_id: str,
        variances_sharded: Optional[ShardedREBank] = None,
    ):
        self.sharded_bank = sharded_bank
        self.re_dataset = re_dataset
        self.random_effect_type = random_effect_type
        self.feature_shard_id = feature_shard_id
        self.variances_sharded = variances_sharded
        self._bank_cache: Optional[Array] = None
        self._var_cache: Optional[Array] = None

    @property
    # photon: sharding(export)
    def bank(self) -> Array:
        if self._bank_cache is None:
            self._bank_cache = self.sharded_bank.to_global()
        return self._bank_cache

    @property
    # photon: sharding(export)
    def variances(self) -> Optional[Array]:
        if self.variances_sharded is None:
            return None
        if self._var_cache is None:
            self._var_cache = self.variances_sharded.to_global()
        return self._var_cache

    @variances.setter
    def variances(self, value) -> None:  # dataclass-replace compatibility
        self._var_cache = value

    def to_random_effect_model(self) -> RandomEffectModel:
        """Materialized replicated twin (model artifacts)."""
        return RandomEffectModel(
            self.bank,
            self.re_dataset,
            self.random_effect_type,
            self.feature_shard_id,
            variances=self.variances,
        )
