"""Per-iteration residual routing over ICI: the consumer of
parallel.shuffle.entity_all_to_all.

Reference: every coordinate-descent sweep re-keys the residual scores from
rows to entity groups with a Spark shuffle
(RandomEffectDataSet.addScoresToOffsets, data/RandomEffectDataSet.scala:
55-74; KeyValueScore joins). Round 2 replaced that per iteration with a
full replicated broadcast of the [n] residual vector + a device-side
gather. Here the re-key is the real ICI collective: rows live sharded
over the mesh's data axis, ONE ``lax.all_to_all`` routes each row's
residual to the device that owns its entity's bucket slot, and a local
scatter lands it at the exact (entity row, sample column) the solver
reads — per-row traffic moves each value once instead of replicating the
whole vector to every device.

All routing metadata (owner device, destination slot, send capacities) is
STATIC per (dataset, mesh): computed host-side once from the bucket
layout and reused every iteration.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu import ownership
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.parallel.shuffle import entity_all_to_all

Array = jnp.ndarray


class ResidualRouter:
    """Routes a row-aligned offsets vector to per-bucket entity slabs.

    The destination layout matches RandomEffectOptimizationProblem's
    entity sharding (``_shard_entity_axis``): bucket ``b``'s entities are
    padded to ``n_dev * E_loc_b`` and split contiguously, so entity
    position ``p`` lives on device ``p // E_loc_b`` at local row
    ``p % E_loc_b``. Each device holds one flat buffer of
    ``sum_b E_loc_b * S_b`` offset slots; bucket ``b``'s slab is the
    contiguous slice starting at ``self.starts[b]``.
    """

    def __init__(self, mesh, dataset: RandomEffectDataset, axis: Optional[str] = None):
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        n_dev = int(mesh.shape[self.axis])
        self.n_dev = n_dev

        n = dataset.row_entity_codes.shape[0]
        self.num_rows = n
        n_pad = ((n + n_dev - 1) // n_dev) * n_dev
        self.num_rows_padded = n_pad

        dest_dev = np.full(n_pad, -1, np.int32)
        flat_pos = np.zeros(n_pad, np.int32)
        self.starts: List[int] = []
        self.e_locs: List[int] = []
        flat_len = 0
        for b in dataset.buckets:
            e_b, s_b = b.num_entities, b.capacity
            e_loc = -(-e_b // n_dev)
            self.starts.append(flat_len)
            self.e_locs.append(e_loc)
            ent, col = np.nonzero(b.row_index >= 0)
            rows = b.row_index[ent, col]
            dest_dev[rows] = (ent // e_loc).astype(np.int32)
            flat_pos[rows] = (
                flat_len + (ent % e_loc) * s_b + col
            ).astype(np.int32)
            flat_len += e_loc * s_b
        self.flat_len = flat_len

        # exact static send capacity: worst (source shard -> owner) count
        per_src = n_pad // n_dev
        worst = 1
        for s in range(n_dev):
            local = dest_dev[s * per_src:(s + 1) * per_src]
            local = local[local >= 0]
            if local.size:
                worst = max(
                    worst, int(np.bincount(local, minlength=n_dev).max())
                )
        self.cap = ((worst + 7) // 8) * 8

        row_sharding = NamedSharding(mesh, P(self.axis))
        self._dest_dev = jax.device_put(jnp.asarray(dest_dev), row_sharding)
        self._flat_pos = jax.device_put(jnp.asarray(flat_pos), row_sharding)
        self._row_sharding = row_sharding

        flat_len_ = flat_len
        axis_ = self.axis

        # photon: sharding(axes=[data], in=[data,data,data], out=[data])
        @jax.jit
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis_), P(axis_), P(axis_)),
            out_specs=P(axis_),
            check_vma=False,
        )
        def _scatter_local(codes, vals, pos):
            valid = codes >= 0
            idx = jnp.where(valid, pos, flat_len_)  # trash slot
            buf = jnp.zeros((flat_len_ + 1,), jnp.float32)
            buf = buf.at[idx].set(
                jnp.where(valid, vals, 0.0), mode="drop"
            )
            return buf[:flat_len_]

        self._scatter_local = _scatter_local

    def route(self, offsets: Array) -> Array:
        """[n] row offsets -> [n_dev * flat_len] per-device slab buffers
        (sharded over the data axis). One all_to_all + one local scatter;
        overflow is impossible (capacities are exact static counts)."""
        off = jnp.asarray(offsets, jnp.float32)
        if off.shape[0] != self.num_rows_padded:
            off = jnp.concatenate([
                off, jnp.zeros((self.num_rows_padded - off.shape[0],), jnp.float32)
            ])
        off = jax.device_put(off, self._row_sharding)
        shuffled = entity_all_to_all(
            self.mesh, self._dest_dev,
            (off, self._flat_pos),
            cap=self.cap, axis=self.axis,
        )
        vals, pos = shuffled.payload
        return self._scatter_local(shuffled.entity_codes, vals, pos)

    def bucket_slab(self, flat: Array, bucket_index: int, capacity: int) -> Array:
        """Slice bucket ``bucket_index``'s offsets slab out of a routed
        buffer -> [n_dev * E_loc, S] (entity-sharded like the solver's
        bucket arrays)."""
        s = self.starts[bucket_index]
        e_loc = self.e_locs[bucket_index]
        per_dev = flat.reshape(self.n_dev, self.flat_len)
        slab = per_dev[:, s:s + e_loc * capacity]
        return slab.reshape(self.n_dev * e_loc, capacity)


class PodResidualRouter:
    """Two-hop residual exchange for HASH-sharded entity banks
    (game/pod.py): rows live row-sharded over the mesh axis, entity
    ``e`` lives on shard ``e % n_dev`` — the LongHashPartitioner analog,
    matching ``parallel.shuffle``'s ownership rule.

    Hop 1 (:meth:`route_in`): ONE ``lax.all_to_all`` carries each row's
    residual to its entity's owner shard, landing in a static per-owner
    SLOT layout. Hop 2 (fused into the pod scoring program): the owner
    scores its slots against its local bank rows and the same
    ``all_to_all`` pattern, reversed, carries the scores back to the
    rows. Per-row traffic per CD iteration is two floats — the residual
    in and the score out — with zero host-side gathers anywhere on the
    path (the regression tests count the ``overlap.device_get`` seam).

    All routing metadata is STATIC per (row entity codes, mesh): the
    send position of every row (``owner * cap + rank``) doubles as its
    return position, because ``all_to_all`` is its own inverse on the
    [n_dev, cap] block layout.
    """

    def __init__(self, mesh, row_entity_codes, *, axis: Optional[str] = None):
        self.mesh = mesh
        self.axis = axis or mesh.axis_names[0]
        n_dev = int(mesh.shape[self.axis])
        self.n_dev = n_dev

        codes = np.asarray(row_entity_codes, np.int64)
        n = codes.shape[0]
        self.num_rows = n
        n_pad = ((n + n_dev - 1) // n_dev) * n_dev
        self.num_rows_padded = n_pad
        per_src = n_pad // n_dev
        owner = np.full(n_pad, -1, np.int64)
        owner[:n] = np.where(
            codes >= 0, ownership.owner_of(codes, n_dev), -1
        )

        # rank of each row among same-owner rows WITHIN its source shard
        # (the row-sharded block it lives in), plus the exact capacity —
        # the worst (source, owner) count, so overflow is impossible
        rank = np.zeros(n_pad, np.int64)
        cap = 1
        for s in range(n_dev):
            blk = owner[s * per_src:(s + 1) * per_src]
            for o in range(n_dev):
                m = blk == o
                c = int(m.sum())
                if c:
                    rank[s * per_src:(s + 1) * per_src][m] = np.arange(c)
                    cap = max(cap, c)
        cap = ((cap + 7) // 8) * 8
        self.cap = cap
        self.num_slots = n_dev * cap  # per-owner received slot count

        # what one hop moves: every owned row, and of those the rows whose
        # owner is another device than the one that holds the row
        self.num_routed_rows = int(np.count_nonzero(owner >= 0))
        self.cross_shard_rows = int(np.count_nonzero(
            (owner >= 0) & (owner != np.arange(n_pad) // per_src)
        ))

        # send position == return position: owner * cap + rank; invalid
        # rows point at the trash slot (num_slots)
        send_pos = np.where(
            owner >= 0, owner * cap + rank, self.num_slots
        ).astype(np.int32)
        # owner-side inverse tables (host): which global row landed in
        # slot (src * cap + rank) of owner o — the pod data layer builds
        # its per-slot feature/code arrays from these
        slot_row = np.full((n_dev, self.num_slots), -1, np.int64)
        rows = np.nonzero(owner >= 0)[0]
        src = rows // per_src
        slot_row[owner[rows], src * cap + rank[rows]] = rows
        self.slot_row = slot_row  # [owner, slot] -> global row id, -1 pad
        # source-side slot of each row ON ITS OWNER: src * cap + rank
        self.slot_of_row = np.where(
            owner >= 0,
            (np.arange(n_pad) // per_src) * cap + rank,
            -1,
        ).astype(np.int64)

        row_sharding = NamedSharding(mesh, P(self.axis))
        self._row_sharding = row_sharding
        self._send_pos = jax.device_put(jnp.asarray(send_pos), row_sharding)

        cap_ = cap
        n_dev_ = n_dev
        axis_ = self.axis

        # (the functions handed to jax.jit name the XLA modules)
        # photon: sharding(axes=[entity], in=[entity,entity], out=[entity])
        @jax.jit
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis_), P(axis_)),
            out_specs=P(axis_),
            check_vma=False,
        )
        def pod_route_in(vals, pos):
            buf = jnp.zeros((n_dev_ * cap_ + 1,), jnp.float32)
            buf = buf.at[pos].set(vals, mode="drop")[:-1]
            blocks = buf.reshape(n_dev_, cap_)
            out = lax.all_to_all(
                blocks, axis_, split_axis=0, concat_axis=0, tiled=False
            )
            return out.reshape(-1)

        self._route_in = pod_route_in

        # photon: sharding(axes=[entity], in=[entity,entity], out=[entity])
        @jax.jit
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis_), P(axis_)),
            out_specs=P(axis_),
            check_vma=False,
        )
        def pod_route_out(slot_vals, pos):
            blocks = slot_vals.reshape(n_dev_, cap_)
            back = lax.all_to_all(
                blocks, axis_, split_axis=0, concat_axis=0, tiled=False
            ).reshape(-1)
            safe = jnp.minimum(pos, n_dev_ * cap_ - 1)
            return jnp.where(pos < n_dev_ * cap_, back[safe], 0.0)

        self._route_out = pod_route_out

    def _pad_rows(self, vec: Array) -> Array:
        vec = jnp.asarray(vec, jnp.float32)
        if vec.shape[0] != self.num_rows_padded:
            vec = jnp.concatenate([
                vec,
                jnp.zeros(
                    (self.num_rows_padded - vec.shape[0],), jnp.float32
                ),
            ])
        return jax.device_put(vec, self._row_sharding)

    def route_in(self, row_values: Array) -> Array:
        """[n] row values -> [n_dev * num_slots] owner-slot values
        (sharded over the axis). One all_to_all; no host round trip."""
        return self._route_in(self._pad_rows(row_values), self._send_pos)

    def route_out(self, slot_values: Array) -> Array:
        """[n_dev * num_slots] owner-slot values -> [num_rows_padded]
        row-aligned values (sharded). The reverse all_to_all of
        :meth:`route_in`; rows with no owner (padding) read 0."""
        return self._route_out(slot_values, self._send_pos)
