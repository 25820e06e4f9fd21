"""Random-effect dataset: per-entity data as bucketed dense blocks.

Reference: photon-ml .../data/RandomEffectDataSet.scala (activeData grouped
per entity with reservoir cap + weight rescale at :254-317, passive split
at :328-369), data/LocalDataSet.scala (Pearson feature filter :116-130,
scorer :202+), projector/IndexMapProjector.scala:83-105 (per-entity dense
re-indexing), ProjectionMatrix.scala:90-119 (shared Gaussian random
projection, intercept-preserving), RandomEffectDataSetPartitioner.scala
(entity load balancing).

TPU-native shape: the groupByKey shuffle becomes a host-side stable sort;
entities are packed into BUCKETS of equal sample capacity (power-of-two)
so per-entity solves vmap over [E_b, S_b, k] dense blocks with weight-0
padding — the "millions of tiny LBFGS solves" run as ONE XLA program per
bucket (SURVEY P2: entities are the expert-parallel analog).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import cached_property
from typing import List, NamedTuple, Optional

import numpy as np

from photon_ml_tpu.game.config import (
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.data import GameDataset, ShardData
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.obs.trace import record_elapsed
from photon_ml_tpu.obs.trace import span as obs_span


class RowRuns(NamedTuple):
    """A bucket whose every entity's real slots hold CONSECUTIVE rows in
    slot order (``row_index[e, j] == starts[e] + j`` for ``j <
    counts[e]``, -1 after): the bucket's ``row_index`` in 2 x [E_b].
    The same pair says where an entity's rows sit in the residual SORTED
    into the dataset's entity order (:func:`observe_entity_order`), for a
    bucket whose rows are no runs in the row vector itself."""

    starts: np.ndarray  # int32 [E_b]; 0 for an entity with no real slot
    counts: np.ndarray  # int32 [E_b]


def observe_row_runs(row_index: np.ndarray) -> Optional[RowRuns]:
    """The :class:`RowRuns` of ``row_index`` [E_b, S_b], or None where
    some entity's rows are not a run: read from the data alone (a table
    grouped by the entity has them; a shuffled one, or an entity whose
    reservoir cap dropped a middle row, does not)."""
    counts = (row_index >= 0).sum(axis=1).astype(np.int32)
    starts = np.where(counts > 0, row_index[:, 0], 0).astype(np.int32)
    slot = np.arange(row_index.shape[1], dtype=np.int32)[None, :]
    runs = np.where(slot < counts[:, None], starts[:, None] + slot, -1)
    if not np.array_equal(runs, row_index):
        return None
    return RowRuns(starts, counts)


class EntityOrder(NamedTuple):
    """The order that makes every entity's rows a run in a SORTED copy of
    the residual, for the buckets that observe no runs in the row vector
    (:func:`observe_entity_order`), and its inverse, which brings a vector
    written in that order back to row order (a bank's scores,
    ``game/random_effect.re_score``): sorting by ``keys`` takes the row
    vector into the order, sorting by ``rows`` takes it back."""

    keys: np.ndarray  # int32 [n]: each row's position in the order
    rows: np.ndarray  # int32 [n]: the row at each position of the order
    # a bucket: its RowRuns in the sorted vector; None for a bucket of runs
    # in the row vector or one whose real slots are not a prefix
    runs: List[Optional[RowRuns]]


def observe_entity_order(buckets, num_rows: int) -> Optional[EntityOrder]:
    """The ENTITY ORDER of the rows held by the buckets that observe no
    runs (``row_runs`` None), read from their ``row_index`` alone. The
    order puts those buckets' rows bucket after bucket, entity after
    entity, each entity's in slot order; ``keys`` int32 [num_rows] is each
    row's position in it (a row no such bucket holds comes after the
    last, in row order), so that sorting the residual by ``keys`` makes
    every such entity's rows a run, at the bucket's ``runs``; ``rows``,
    its inverse, is those buckets' rows as they come then the rest, made
    with the keys and never sorted for. A bucket
    whose real slots are not a prefix of its slots gets none; None where
    no bucket has them or a row is held twice (no permutation then)."""
    runs, held, at = [], [], 0
    for bucket in buckets:
        real = bucket.row_index >= 0
        counts = real.sum(axis=1, dtype=np.int32)
        slot = np.arange(real.shape[1], dtype=np.int32)[None, :]
        if bucket.row_runs is not None or not np.array_equal(
            real, slot < counts[:, None]
        ):
            runs.append(None)
            continue
        starts = at + np.cumsum(counts) - counts
        runs.append(RowRuns(
            np.where(counts > 0, starts, 0).astype(np.int32), counts
        ))
        held.append(bucket.row_index[real])
        at += int(counts.sum())
    if not held:
        return None
    held = np.concatenate(held).astype(np.int32)
    keys = np.full(num_rows, -1, np.int32)
    keys[held] = np.arange(at, dtype=np.int32)
    rest = np.flatnonzero(keys < 0).astype(np.int32)
    if len(rest) != num_rows - at:  # a row held twice
        return None
    keys[rest] = np.arange(at, num_rows, dtype=np.int32)
    return EntityOrder(keys, np.concatenate([held, rest]), runs)


@dataclass
class RandomEffectBucket:
    """Entities with <= capacity active samples, dense-packed."""

    entity_codes: np.ndarray  # int32 [E_b]
    row_index: np.ndarray  # int32 [E_b, S_b] global row id, -1 pad
    indices: np.ndarray  # int32 [E_b, S_b, k] LOCAL feature indices, 0 pad
    values: np.ndarray  # float32 [E_b, S_b, k]
    labels: np.ndarray  # float32 [E_b, S_b]
    offsets: np.ndarray  # float32 [E_b, S_b]
    weights: np.ndarray  # float32 [E_b, S_b] (0 pad; reservoir-rescaled)
    # True when ``indices`` is the tiled arange(k) (k == local_dim, the
    # MF latent view): the dense solvers then use X = values directly,
    # skipping the [E, S, k, D] densify broadcast entirely
    identity_indices: bool = False
    # int32 [E_b, S_b], -1 pad: what a values override makes a slot's
    # values from (game/random_effect.ValuesOverride; an ALS half-step:
    # the partner entity's code). Such a bucket stores no values, and an
    # identity one no indices: both are [E_b, S_b, 0]
    override_keys: Optional[np.ndarray] = None
    # observed where the bucket is built (:func:`observe_row_runs`), not
    # configured: where set, ``update_bank`` reads the residual as [E_b]
    # windows of the row vector and uploads no ``row_index`` for it
    row_runs: Optional[RowRuns] = None

    @property
    def num_entities(self) -> int:
        return self.entity_codes.shape[0]

    @property
    def capacity(self) -> int:
        return self.row_index.shape[1]


@dataclass
class RandomEffectDataset:
    """Active data bucketed per entity + row-aligned local projections."""

    config: RandomEffectDataConfiguration
    num_entities: int
    local_dim: int  # D: width of the entity model bank
    # per-entity projection: global feature id per local slot, -1 pad
    projection: np.ndarray  # int32 [E, D]
    # Row-aligned views over the FULL dataset (active + passive + unseen):
    # local feature indices per row (0 pad; unseen features dropped).
    row_local_indices: np.ndarray  # int32 [n, k]
    row_local_values: np.ndarray  # float32 [n, k]
    row_entity_codes: np.ndarray  # int32 [n] (-1 for padding rows)
    buckets: List[RandomEffectBucket]
    num_active_rows: int
    num_passive_rows: int
    # RANDOM projector only: [d_global, D] projection matrix
    random_projection: Optional[np.ndarray] = None

    @cached_property
    def entity_order(self) -> Optional[EntityOrder]:
        """The buckets' :class:`EntityOrder` over the ``[n]`` rows,
        observed when a replicated bank update first reads the residual,
        or its blocks' scores are first placed into the row vector
        (``game/random_effect._residual_args``), and kept: a dataset that
        does neither (the pod's, a streamed segment's) never builds it."""
        with obs_span(
            "re.entity_order", rows=self.row_entity_codes.shape[0],
            buckets=len(self.buckets),
        ):
            return observe_entity_order(
                self.buckets, self.row_entity_codes.shape[0]
            )

    @property
    def intercept_local_index(self) -> Optional[int]:
        return self._intercept_local

    _intercept_local: Optional[int] = None


def build_random_effect_dataset(
    dataset: GameDataset,
    config: RandomEffectDataConfiguration,
    *,
    seed: int = 0,
) -> RandomEffectDataset:
    """GameDataset + config -> bucketed per-entity dataset.

    Mirrors RandomEffectDataSet.buildWithConfiguration: group by entity,
    reservoir-cap active data with weight rescale cnt/cap, passive split,
    optional Pearson filter, per-entity index (or shared random)
    projection.

    The reference does this as a distributed groupByKey shuffle
    (RandomEffectDataSet.scala:169-369); here the whole build is a handful
    of argsort/bincount/flat-scatter passes — no per-row or per-entity
    Python loops — so one host saturates (1M rows x 8 nnz with 100k
    entities builds in ~2-3 s vs ~13 s/1M rows for the round-2 loop
    build; the unique() sort over entity-feature keys dominates).

    The build is the span ``re.dataset_build``, sized by its attrs.
    """
    with obs_span(
        "re.dataset_build", type=config.random_effect_type,
        rows=dataset.num_rows,
    ) as build_span:
        ds = _build_random_effect_dataset(dataset, config, seed)
        build_span.set(entities=ds.num_entities, buckets=len(ds.buckets))
    return ds


def _build_random_effect_dataset(
    dataset: GameDataset, config: RandomEffectDataConfiguration, seed: int
) -> RandomEffectDataset:
    shard: ShardData = dataset.shards[config.feature_shard_id]
    codes = np.asarray(dataset.entity_codes[config.random_effect_type])
    eindex = dataset.entity_indexes[config.random_effect_type]
    E = eindex.num_entities
    n = dataset.num_rows
    k = shard.indices.shape[1]
    rng = np.random.default_rng(seed)

    real = np.asarray(dataset.weights) > 0
    valid = real & (codes >= 0)
    labels = np.asarray(dataset.labels)
    offsets = np.asarray(dataset.offsets)
    weights = np.asarray(dataset.weights)

    # --- group rows by entity (the groupByKey analog: one stable sort) ---
    vrows = np.nonzero(valid)[0]
    scodes = codes[vrows]
    order = np.argsort(scodes, kind="stable")
    srows = vrows[order]  # grouped by entity, ascending row id within
    scodes = scodes[order]
    counts = np.bincount(scodes, minlength=E)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])

    # --- reservoir cap with weight rescale cnt/cap -----------------------
    # (RandomEffectDataSet.scala:254-317). Uniform without-replacement
    # sampling per over-cap entity: random priority per row, keep the cap
    # best-ranked priorities within each entity.
    cap = config.active_data_upper_bound
    if cap is not None and len(srows):
        pri = rng.random(len(srows))
        po = np.lexsort((pri, scodes))
        pri_rank = np.empty(len(srows), np.int64)
        pri_rank[po] = np.arange(len(srows)) - starts[scodes[po]]
        keep_active = pri_rank < cap
        scale_e = np.where(counts > cap, counts / max(cap, 1), 1.0)
        num_passive = int(np.maximum(counts - cap, 0).sum())
    else:
        keep_active = np.ones(len(srows), bool)
        scale_e = np.ones(E)
        num_passive = 0
    arows = srows[keep_active]
    acodes = scodes[keep_active]
    acounts = np.bincount(acodes, minlength=E)
    astarts = np.concatenate([[0], np.cumsum(acounts)[:-1]])
    arank = np.arange(len(arows)) - astarts[acodes]
    num_active = int(acounts.sum())

    # --- per-entity feature selection + local projection + row remap -----
    dim = shard.dim
    proj_type = config.projector_type
    random_projection = None
    intercept_local: Optional[int] = None
    local_dims = None  # features of each entity's own space, where it has one

    if proj_type == ProjectorType.IDENTITY:
        D = max(dim, 1)
        projection = np.full((E, D), -1, np.int32)
        projection[:] = np.arange(D, dtype=np.int32)[None, :]
        if shard.intercept_index is not None:
            intercept_local = shard.intercept_index
        row_local_ix = shard.indices.copy()
        row_local_v = shard.values.copy()
    elif proj_type == ProjectorType.RANDOM:
        D = max(int(config.random_projection_dim), 1)
        # Gaussian N(0, 1/D), intercept column preserved
        # (ProjectionMatrix.scala:90-119).
        random_projection = rng.normal(
            0.0, 1.0 / np.sqrt(D), size=(dim, D)
        ).astype(np.float32)
        if shard.intercept_index is not None:
            random_projection[shard.intercept_index, :] = 0.0
            random_projection[:, D - 1] = np.where(
                np.arange(dim) == shard.intercept_index, 1.0, 0.0
            )
            intercept_local = D - 1
        projection = np.full((E, D), -1, np.int32)
        # dense projected rows: x_local = x . P  [D]
        kk = max(k, D)
        row_local_ix = np.zeros((n, kk), np.int32)
        row_local_v = np.zeros((n, kk), np.float32)
        row_local_ix[:, :D] = np.arange(D, dtype=np.int32)[None, :]
        chunk = max(1, (1 << 22) // max(D, 1))  # bound gather temp memory
        for s in range(0, len(vrows), chunk):
            rs = vrows[s:s + chunk]
            vals = shard.values[rs]  # [c, k]
            proj = random_projection[shard.indices[rs]]  # [c, k, D]
            row_local_v[rs, :D] = np.einsum(
                "ck,ckd->cd", vals, proj, optimize=True
            )
    else:  # INDEX_MAP: per-entity dense re-indexing of active features
        # (IndexMapProjector.scala:83-105). ONE unique(return_inverse) over
        # the live entries of every valid row replaces the per-entity set
        # building AND every later lookup: a per-key "kept" mask (active
        # membership / Pearson top-k / intercept) defines the map, the
        # inverse positions remap every row — no searchsorted anywhere.
        # The span ``re.index_map_build``, sized by its attrs.
        t_map = time.perf_counter()
        ratio = config.features_to_samples_ratio
        srow_of_entry = np.repeat(np.arange(len(srows)), k)
        slot_of_entry = np.tile(np.arange(k), len(srows))
        ft = shard.indices[srows].ravel().astype(np.int64)
        vv = shard.values[srows].ravel()
        live = vv != 0
        e_srow = srow_of_entry[live]
        e_slot = slot_of_entry[live]
        e_val = vv[live]
        ekeys = scodes[e_srow].astype(np.int64) * dim + ft[live]
        n_live = len(ekeys)
        if shard.intercept_index is not None:
            # intercept key for EVERY entity (always in the map, even for
            # entities with no active rows)
            icept = (
                np.arange(E, dtype=np.int64) * dim + shard.intercept_index
            )
            ekeys = np.concatenate([ekeys, icept])
        uniq, inv = np.unique(ekeys, return_inverse=True)
        inv_live = inv[:n_live]
        U = len(uniq)
        code_u = uniq // dim
        feat_u = uniq % dim
        counts_u = np.bincount(code_u, minlength=E)
        starts_u = np.concatenate([[0], np.cumsum(counts_u)[:-1]])

        entry_active = keep_active[e_srow]
        kept = np.zeros(U, bool)
        if ratio is None:
            if cap is None:
                kept[:] = True
            else:
                # map = features seen in at least one ACTIVE entry
                kept[inv_live[entry_active]] = True
                if shard.intercept_index is not None:
                    kept[inv[n_live:]] = True
        else:
            # Pearson top-k per entity over the ACTIVE entries
            # (LocalDataSet.filterFeaturesByPearsonCorrelationScore:116-130)
            lab_s = labels[srows].astype(np.float64)
            m_safe = np.maximum(acounts, 1)
            ybar = (
                np.bincount(
                    scodes[keep_active], weights=lab_s[keep_active],
                    minlength=E,
                )
                / m_safe
            )
            yc_s = np.where(keep_active, lab_s - ybar[scodes], 0.0)
            y_var = np.bincount(scodes, weights=yc_s**2, minlength=E) / m_safe
            va = np.where(entry_active, e_val.astype(np.float64), 0.0)
            x_sum = np.bincount(inv_live, weights=va, minlength=U)
            x2_sum = np.bincount(inv_live, weights=va * va, minlength=U)
            xy_sum = np.bincount(
                inv_live, weights=va * yc_s[e_srow], minlength=U
            )
            cand = np.zeros(U, bool)
            cand[inv_live[entry_active]] = True
            m = acounts[code_u].astype(np.float64)
            m = np.maximum(m, 1.0)
            x_mean = x_sum / m
            x_var = x2_sum / m - x_mean**2
            denom = np.sqrt(np.maximum(x_var * y_var[code_u], 1e-30))
            corr = np.where(denom > 1e-15, np.abs(xy_sum / m) / denom, 0.0)
            corr = np.where(cand, corr, -np.inf)
            if shard.intercept_index is not None:
                cand[inv[n_live:]] = True
                corr = np.where(feat_u == shard.intercept_index, np.inf, corr)
            num_keep = np.maximum(
                1, np.ceil(ratio * acounts[code_u])
            ).astype(np.int64)
            order_u = np.lexsort((-corr, code_u))
            rank = np.arange(U) - starts_u[code_u[order_u]]
            kept[order_u] = rank < num_keep[order_u]
            kept &= cand

        # local index of each kept key = its rank among kept within entity
        kept_cum = np.cumsum(kept)
        kept_before = np.concatenate([[0], kept_cum])[starts_u]
        local_u = (kept_cum - 1) - kept_before[code_u]  # valid where kept
        local_dims = np.bincount(code_u[kept], minlength=E)
        D = max(int(local_dims.max()) if U else 1, 1)
        projection = np.full((E, D), -1, np.int32)
        if U:
            projection[code_u[kept], local_u[kept]] = feat_u[kept].astype(
                np.int32
            )

        # row remap over the FULL valid table (active + passive rows;
        # filtered-out features drop to 0-slots)
        row_local_ix = np.zeros((n, k), np.int32)
        row_local_v = np.zeros((n, k), np.float32)
        entry_kept = kept[inv_live]
        er = srows[e_srow[entry_kept]]
        es = e_slot[entry_kept]
        row_local_ix[er, es] = local_u[inv_live[entry_kept]].astype(np.int32)
        row_local_v[er, es] = e_val[entry_kept]
        record_elapsed(
            "re.index_map_build", t_map, time.perf_counter(), entities=E,
            distinct_keys=U, max_local_dim=D,
        )

    # --- bucketed active data (power-of-two capacities) ------------------
    # one flat scatter per bucket instead of per-entity/per-row fills
    caps_arr = np.zeros(E, np.int64)
    nz_e = acounts > 0
    caps_arr[nz_e] = 1 << np.ceil(
        np.log2(np.maximum(acounts[nz_e], 1))
    ).astype(np.int64)
    buckets: List[RandomEffectBucket] = []
    kk = row_local_ix.shape[1]
    row_scale = scale_e[acodes]  # reservoir weight rescale per active row
    for S in sorted(set(caps_arr[nz_e].tolist())):
        members = np.nonzero(caps_arr == S)[0]
        E_b = len(members)
        in_bucket = caps_arr[acodes] == S
        br = arows[in_bucket]  # global row ids, grouped by entity
        # entity -> dense slot in this bucket
        b_pos = np.searchsorted(members, acodes[in_bucket])
        b_slot = arank[in_bucket]
        b_rows = np.full((E_b, S), -1, np.int32)
        b_ix = np.zeros((E_b, S, kk), np.int32)
        b_v = np.zeros((E_b, S, kk), np.float32)
        b_lab = np.zeros((E_b, S), np.float32)
        b_off = np.zeros((E_b, S), np.float32)
        b_w = np.zeros((E_b, S), np.float32)
        b_rows[b_pos, b_slot] = br.astype(np.int32)
        b_ix[b_pos, b_slot] = row_local_ix[br]
        b_v[b_pos, b_slot] = row_local_v[br]
        b_lab[b_pos, b_slot] = labels[br]
        b_off[b_pos, b_slot] = offsets[br]
        b_w[b_pos, b_slot] = weights[br] * row_scale[in_bucket]
        buckets.append(
            RandomEffectBucket(
                entity_codes=members.astype(np.int32),
                row_index=b_rows,
                indices=b_ix,
                values=b_v,
                labels=b_lab,
                offsets=b_off,
                weights=b_w,
                # observed, not configured: a ONE-feature local space (an
                # intercept-only shard: a bias per entity) IS the arange
                # of itself, so X is values, to the bit (a sum of one
                # term; a padding slot's value is zero)
                identity_indices=bool(
                    kk == D == 1 and not row_local_ix[br].any()
                ),
                row_runs=observe_row_runs(b_rows),
            )
        )

    _count_built(
        config.random_effect_type, buckets, acounts, local_dims, D,
        num_passive, int((counts > cap).sum()) if cap is not None else 0,
    )
    ds = RandomEffectDataset(
        config=config,
        num_entities=E,
        local_dim=D,
        projection=projection,
        row_local_indices=row_local_ix,
        row_local_values=row_local_v,
        row_entity_codes=np.where(real, codes, -1).astype(np.int32),
        buckets=buckets,
        num_active_rows=num_active,
        num_passive_rows=num_passive,
        random_projection=random_projection,
    )
    ds._intercept_local = intercept_local
    return ds


def _count_built(
    effect_type: str, buckets, active_counts, local_dims, d_local: int,
    passive_rows: int, capped: int,
) -> None:
    """One dataset build into the registry, by random-effect type: its
    active and passive rows, the entities the reservoir cap cut, the
    capacity classes (``photon_re_capacity_classes_total`` over
    ``photon_re_dataset_builds_total``), and the slots of the blocks the
    solves stage, ``[E_b, S_b, D]`` a bucket, that hold a row on a
    dimension of its entity's own space (``held``) or no row or no such
    dimension (``padding``). ``local_dims`` None: every entity's space is
    all ``d_local`` wide. Host arithmetic on what the build made."""
    registry = default_registry()
    labels = {"type": effect_type}
    rows = registry.counter(
        "photon_re_rows_total",
        "rows of the random-effect datasets built, by type and state "
        "(active | passive)",
    )
    for state, count in (
        ("active", int(active_counts.sum())), ("passive", passive_rows)
    ):
        if count:
            rows.inc(count, state=state, **labels)
    if capped:
        registry.counter(
            "photon_re_capped_entities_total",
            "entities whose active rows the reservoir cap cut, by type",
        ).inc(capped, **labels)
    registry.counter(
        "photon_re_dataset_builds_total",
        "random-effect datasets built, by type",
    ).inc(1, **labels)
    registry.counter(
        "photon_re_capacity_classes_total",
        "capacity classes (buckets) of the random-effect datasets built, "
        "by type",
    ).inc(len(buckets), **labels)
    dims = (
        np.full(active_counts.shape, d_local, np.int64)
        if local_dims is None else local_dims.astype(np.int64)
    )
    held = int(np.sum(active_counts.astype(np.int64) * dims))
    staged = sum(b.row_index.size for b in buckets) * d_local
    slots = registry.counter(
        "photon_re_bank_slots_total",
        "slots of the blocks the random-effect solves stage, by type and "
        "state (held | padding)",
    )
    for state, count in (("held", held), ("padding", staged - held)):
        if count:
            slots.inc(count, state=state, **labels)
