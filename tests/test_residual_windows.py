"""The residual reaches a bank block as windows of the row vector where an
entity's rows are a run (game/random_effect_data.observe_row_runs,
game/random_effect._residual_program), and as windows of the residual
sorted into the dataset's entity order where they are not
(observe_entity_order): runs and the order are observed from the data
alone, the order only where a bank update reads the residual, both
window paths are the element gather's values to the bit, an update on
either equals the same update on the slot path, and
``photon_bank_residual_slots_total`` says which path the slots took."""

from dataclasses import replace

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.config import RandomEffectDataConfiguration
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    _residual_program,
    _split_bucket,
)
from photon_ml_tpu.game.random_effect_data import (
    RandomEffectBucket,
    RowRuns,
    build_random_effect_dataset,
    EntityOrder,
    observe_entity_order,
    observe_row_runs,
)
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.utils.index_map import IndexMap, feature_key


def _table(codes, d=12, k=4, seed=0, items=None):
    """A GameDataset over the given entity (``items``: and item) of each
    row."""
    rng = np.random.default_rng(seed)
    n, E = len(codes), int(codes.max()) + 1
    entity_codes = {"user": codes.astype(np.int32)}
    entity_indexes = {"user": EntityIndex.build(
        "user", [f"e{i:03d}" for i in range(E)]
    )}
    if items is not None:
        entity_codes["item"] = items.astype(np.int32)
        entity_indexes["item"] = EntityIndex.build(
            "item", [f"i{i:03d}" for i in range(int(items.max()) + 1)]
        )
    imap = IndexMap.build(
        (feature_key(f"f{i}", "") for i in range(d)), add_intercept=False
    )
    return GameDataset(
        uids=[str(i) for i in range(n)],
        labels=(rng.uniform(size=n) > 0.5).astype(np.float32),
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        shards={"s": ShardData(
            indices=rng.integers(0, d, size=(n, k)).astype(np.int32),
            values=rng.normal(size=(n, k)).astype(np.float32),
            index_map=imap, intercept_index=None,
        )},
        entity_codes=entity_codes,
        entity_indexes=entity_indexes,
        num_real_rows=n,
    )


def _grouped_codes(seed=0, E=40, heaviest=70):
    """Rows grouped by entity, an uneven histogram (several capacity
    classes), the entities in a shuffled order."""
    rng = np.random.default_rng(seed)
    counts = np.minimum(rng.geometric(0.08, size=E), heaviest)
    return np.repeat(rng.permutation(E), counts)


def _build(codes, cap=None, seed=0):
    return build_random_effect_dataset(
        _table(codes),
        RandomEffectDataConfiguration(
            random_effect_type="user", feature_shard_id="s",
            active_data_upper_bound=cap,
        ),
        seed=seed,
    )


def _problem(**kw):
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=5),
        RegularizationContext(RegularizationType.L2), reg_weight=0.5, **kw
    )


def _slots_counted(coordinate):
    counter = default_registry().counter("photon_bank_residual_slots_total")
    return tuple(
        counter.value(coordinate=coordinate, path=path)
        for path in ("windows", "sorted", "slots")
    )


# ---- the build observes runs, from the data alone ----------------------


def test_a_grouped_table_reads_runs_in_every_bucket():
    red = _build(_grouped_codes())
    assert len(red.buckets) >= 3
    for bucket in red.buckets:
        runs = bucket.row_runs
        assert isinstance(runs, RowRuns)
        assert runs.starts.dtype == runs.counts.dtype == np.int32
        held = bucket.row_index >= 0
        np.testing.assert_array_equal(runs.counts, held.sum(axis=1))
        np.testing.assert_array_equal(runs.starts, bucket.row_index[:, 0])
        want = runs.starts[:, None] + np.arange(bucket.capacity)[None, :]
        np.testing.assert_array_equal(bucket.row_index[held], want[held])


def test_a_shuffled_table_reads_no_runs():
    codes = np.random.default_rng(3).permutation(_grouped_codes())
    red = _build(codes)
    wide = [b for b in red.buckets if b.capacity >= 4]
    assert len(wide) >= 3 and all(b.row_runs is None for b in wide)
    # (an entity with ONE row is a run on any table: observed, not assumed)
    for b in red.buckets:
        if b.capacity == 1:
            np.testing.assert_array_equal(b.row_runs.counts, 1)


def test_a_reservoir_cap_that_drops_a_middle_row_reads_no_runs():
    # one class: every entity has 12 rows, the cap keeps 8 of them
    codes = np.repeat(np.arange(30), 12)
    red = _build(codes, cap=8, seed=5)
    (bucket,) = red.buckets
    kept = bucket.row_index
    assert (np.diff(kept, axis=1) > 1).any()  # some entity lost a middle row
    assert bucket.row_runs is None
    assert observe_row_runs(kept) is None


def test_one_broken_entity_breaks_its_bucket_only():
    rows = np.array([[4, 5, 6, -1], [9, 10, -1, -1]], np.int32)
    runs = observe_row_runs(rows)
    np.testing.assert_array_equal(runs.starts, [4, 9])
    np.testing.assert_array_equal(runs.counts, [3, 2])
    for broken in (
        [[4, 6, 5, -1], [9, 10, -1, -1]],  # out of slot order
        [[4, 5, 7, -1], [9, 10, -1, -1]],  # a gap
        [[4, 5, -1, 6], [9, 10, -1, -1]],  # a hole among the slots
    ):
        assert observe_row_runs(np.array(broken, np.int32)) is None
    # an entity with no real slot is an empty run
    empty = observe_row_runs(np.array([[-1, -1], [3, 4]], np.int32))
    np.testing.assert_array_equal(empty.starts, [0, 3])
    np.testing.assert_array_equal(empty.counts, [0, 2])


def test_a_streamed_segments_bucket_reads_no_runs():
    """``game/streaming.py`` builds its buckets with the default: the
    field is observed where a builder chooses to, never assumed."""
    rows = np.arange(8, dtype=np.int32).reshape(2, 4)
    bucket = RandomEffectBucket(
        entity_codes=np.arange(2, dtype=np.int32), row_index=rows,
        indices=np.zeros((2, 4, 1), np.int32),
        values=np.zeros((2, 4, 1), np.float32),
        labels=np.zeros((2, 4), np.float32),
        offsets=np.zeros((2, 4), np.float32),
        weights=np.ones((2, 4), np.float32),
    )
    assert bucket.row_runs is None
    import inspect

    from photon_ml_tpu.game import streaming

    assert "row_runs" not in inspect.getsource(streaming)


# ---- windows equal the element gather, to the bit ----------------------


def _run_rows(rng, n, E, S, *, last_at_end, padding):
    """[E, S] row_index of runs over ``n`` rows: counts 1..S, ``padding``
    entities with none, and (``last_at_end``) one whose window ends at
    row n - 1."""
    counts = rng.integers(1, S + 1, size=E)
    counts[:padding] = 0
    starts = rng.integers(0, n - S, size=E)
    if last_at_end:
        starts[-1] = n - counts[-1]
    slot = np.arange(S)[None, :]
    return np.where(
        slot < counts[:, None], starts[:, None] + slot, -1
    ).astype(np.int32)


def _bucket_of(rows):
    E, S = rows.shape
    return RandomEffectBucket(
        entity_codes=np.arange(E, dtype=np.int32), row_index=rows,
        indices=np.zeros((E, S, 0), np.int32),
        values=np.zeros((E, S, 0), np.float32),
        labels=np.zeros((E, S), np.float32),
        offsets=np.zeros((E, S), np.float32),
        weights=(rows >= 0).astype(np.float32),
        row_runs=observe_row_runs(rows),
    )


def _gathered(residual, rows):
    return np.where(rows >= 0, residual[np.maximum(rows, 0)], np.float32(0))


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


WINDOW_CASES = {
    # name: (capacity, entities, padding entities, rows, window at n - 1)
    "capacity_2": (2, 9, 1, 40, True),
    "capacity_8": (8, 17, 2, 300, True),
    "capacity_32": (32, 33, 3, 1_000, True),
    "capacity_64_no_padding": (64, 20, 0, 2_000, False),
    "capacity_128": (128, 11, 1, 3_000, True),
    "capacity_256": (256, 13, 2, 3_001, True),
    "capacity_1024": (1024, 5, 1, 5_555, True),
    "capacity_4096": (4096, 3, 1, 9_000, True),
    "fewer_rows_than_a_lane_row": (16, 6, 1, 77, True),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_windows_equal_the_element_gather_bitwise(case):
    S, E, padding, n, at_end = WINDOW_CASES[case]
    rng = np.random.default_rng(S + E)
    rows = _run_rows(rng, n, E, S, last_at_end=at_end, padding=padding)
    if at_end:
        assert rows.max() == n - 1
    runs = observe_row_runs(rows)
    assert runs is not None
    residual = rng.normal(size=n).astype(np.float32)
    residual[rng.integers(0, n, size=5)] = -0.0  # a sign a sum would lose
    windows, slots = _residual_program("t")(
        jnp.asarray(residual),
        ((jnp.asarray(runs.starts), jnp.asarray(runs.counts)),
         (jnp.asarray(rows),)),
        paths=(("windows", S), ("slots", 0)),
    )
    want = _gathered(residual, rows)
    assert windows.shape == slots.shape == (E, S)
    np.testing.assert_array_equal(_bits(slots), _bits(want))
    np.testing.assert_array_equal(_bits(windows), _bits(want))


@pytest.mark.parametrize("n_sub", [2, 3])
def test_a_sub_block_split_keeps_the_runs_and_the_values(n_sub):
    rng = np.random.default_rng(n_sub)
    n, E, S = 2_000, 11, 32  # 11 entities: the last sub-block is padded
    bucket = _bucket_of(
        _run_rows(rng, n, E, S, last_at_end=True, padding=0)
    )
    subs = _split_bucket(bucket, n_sub, pad_code=E)
    assert subs[-1].entity_codes[-1] == E  # a padding lane
    residual = rng.normal(size=n).astype(np.float32)
    for sub in subs:
        assert sub.row_runs is not None
        again = observe_row_runs(sub.row_index)
        np.testing.assert_array_equal(sub.row_runs.starts, again.starts)
        np.testing.assert_array_equal(sub.row_runs.counts, again.counts)
    # the folded group: [B, E_sub] starts, [B, E_sub, S] windows
    stacked = tuple(
        jnp.asarray(np.stack(field))
        for field in zip(*(sub.row_runs for sub in subs))
    )
    (windows,) = _residual_program("t")(
        jnp.asarray(residual), (stacked,), paths=(("windows", S),)
    )
    want = np.stack([_gathered(residual, sub.row_index) for sub in subs])
    np.testing.assert_array_equal(_bits(windows), _bits(want))


# ---- update_bank: the two paths give one bank --------------------------


def _without_runs(red):
    """The same dataset with the run and order observations forced off
    (every bucket on the slot path)."""
    off = replace(
        red, buckets=[replace(b, row_runs=None) for b in red.buckets]
    )
    off.entity_order = None
    return off


@pytest.mark.parametrize("case", ["whole_buckets", "split_and_folded"])
def test_update_bank_on_runs_equals_the_slot_path_bitwise(case):
    red = _build(_grouped_codes(seed=4, E=61))
    kw = {}
    if case == "split_and_folded":
        bucket = max(red.buckets, key=lambda b: b.num_entities)
        per_entity = 4 * bucket.capacity * (red.local_dim + bucket.capacity)
        kw["dense_bytes_budget"] = per_entity * -(-bucket.num_entities // 3)
    rng = np.random.default_rng(8)
    bank = jnp.asarray(
        rng.normal(size=(red.num_entities, red.local_dim)).astype(np.float32)
    )
    residual = jnp.asarray(
        rng.normal(size=red.row_entity_codes.shape[0]).astype(np.float32)
    )
    name = f"runs-{case}"
    before = _slots_counted(name)
    problem = _problem(**kw)
    if case == "split_and_folded":
        groups = problem._update_groups(red, red.local_dim)
        assert max(len(members) for members in groups) == 3
    got, _ = problem.update_bank(
        bank, red, residual_offsets=residual, coordinate=name
    )
    counted = tuple(a - b for a, b in zip(_slots_counted(name), before))
    held = sum(b.row_index.size for b in red.buckets)
    assert counted == (held, 0, 0)
    # the same table on the slot path, through a problem of its own
    off = _without_runs(red)
    want, _ = _problem(**kw).update_bank(
        bank, off, residual_offsets=residual, coordinate=name
    )
    counted = tuple(a - b for a, b in zip(_slots_counted(name), before))
    assert counted == (held, 0, held)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # and the variances' pass reads the same offsets
    np.testing.assert_array_equal(
        _bits(problem.bank_variances(got, red, residual)),
        _bits(_problem(**kw).bank_variances(want, off, residual)),
    )


def test_a_mixed_dataset_counts_each_path_by_its_slots():
    red = _build(_grouped_codes(seed=6))
    assert len(red.buckets) >= 2
    # one bucket's observation off: its slots take the gather
    first = red.buckets[0]
    mixed = replace(
        red, buckets=[replace(first, row_runs=None)] + red.buckets[1:]
    )
    mixed.entity_order = None
    rng = np.random.default_rng(2)
    bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
    residual = jnp.asarray(
        rng.normal(size=red.row_entity_codes.shape[0]).astype(np.float32)
    )
    before = _slots_counted("mixed")
    got, _ = _problem().update_bank(
        bank, mixed, residual_offsets=residual, coordinate="mixed"
    )
    windows, sorted_, slots = (
        a - b for a, b in zip(_slots_counted("mixed"), before)
    )
    held = sum(b.row_index.size for b in red.buckets)
    assert sorted_ == 0
    assert slots == first.row_index.size and windows + slots == held
    want, _ = _problem().update_bank(bank, red, residual_offsets=residual)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_a_bucket_of_runs_uploads_no_row_index_for_the_residual():
    red = _build(_grouped_codes(seed=9))
    problem = _problem()
    n = red.row_entity_codes.shape[0]
    problem.update_bank(
        jnp.zeros((red.num_entities, red.local_dim), jnp.float32), red,
        residual_offsets=jnp.zeros((n,), jnp.float32),
    )
    uploaded = {what for _, what in problem._device_cache}
    stacked = red.__dict__.get("_stacked_device_cache", {})
    assert "rows" not in uploaded
    assert not any(key[-1] == "rows" for key in stacked)
    # the slot path uploads it, once, where scoring finds it too
    off = _without_runs(red)
    problem.update_bank(
        jnp.zeros((red.num_entities, red.local_dim), jnp.float32), off,
        residual_offsets=jnp.zeros((n,), jnp.float32),
    )
    assert "rows" in {what for _, what in problem._device_cache} | {
        key[-1] for key in off.__dict__.get("_stacked_device_cache", {})
    }


def test_the_als_structures_observe_runs_on_the_grouped_side_only():
    """A rating table grouped by user: the row half-step reads windows,
    the column half-step, whose movies' rows lie all over it, windows of
    the residual sorted into the movies' order; and the pass equals the
    same pass with the observations forced off."""
    from photon_ml_tpu.game.coordinate import MatrixFactorizationCoordinate
    from photon_ml_tpu.ops.losses import LINEAR

    users = _grouped_codes(seed=12, E=30)
    rng = np.random.default_rng(12)
    items = rng.integers(0, 9, size=len(users))
    ds = _table(users, items=items)

    def coordinate():
        return MatrixFactorizationCoordinate(
            name="mf", dataset=ds, row_effect_type="user",
            col_effect_type="item", num_latent_factors=3,
            problem=RandomEffectOptimizationProblem(
                LINEAR, OptimizerConfig(max_iter=5),
                RegularizationContext(RegularizationType.L2), reg_weight=0.5,
            ),
        )

    mf = coordinate()
    sides = {side: args for side, *args in mf._sides()}
    row = mf._side_structure("row", *sides["row"])
    col = mf._side_structure("col", *sides["col"])
    assert all(b.row_runs is not None for b in row.buckets)
    assert all(b.row_runs is None for b in col.buckets)
    # observed where the update first reads the residual, not at the build
    assert "entity_order" not in col.__dict__
    residual = jnp.asarray(rng.normal(size=len(users)).astype(np.float32))
    before = {c: _slots_counted(c) for c in ("mf_row", "mf_col")}
    got, _ = mf.update_model(mf.initialize_model(), residual)
    counted = {
        c: tuple(a - b for a, b in zip(_slots_counted(c), before[c]))
        for c in before
    }
    assert row.entity_order is None and col.entity_order is not None
    assert counted == {
        "mf_row": (sum(b.row_index.size for b in row.buckets), 0, 0),
        "mf_col": (0, sum(b.row_index.size for b in col.buckets), 0),
    }
    off = coordinate()
    off._als_structure_cache = {
        "row": _without_runs(row), "col": _without_runs(col),
    }
    want, _ = off.update_model(off.initialize_model(), residual)
    np.testing.assert_array_equal(_bits(got.row_latent), _bits(want.row_latent))
    np.testing.assert_array_equal(_bits(got.col_latent), _bits(want.col_latent))


def test_the_program_is_named_for_its_coordinate():
    text = _residual_program("per-user").lower(
        jnp.zeros((10,), jnp.float32),
        ((jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32)),),
        jnp.arange(10, dtype=jnp.int32), paths=(("sorted", 4),),
    ).as_text()
    assert "module @jit_bank_residual_per_user" in text
    assert "sort" in text  # the sort is inside the named program
    assert re_mod._residual_program("per-user") is _residual_program("per-user")


# ---- the sorted path: one sort into the entity order, then windows ------


def test_the_entity_order_is_read_from_row_index_alone():
    loose = np.array([[4, 1, -1], [0, -1, -1], [-1, -1, -1]], np.int32)
    grouped = np.array([[2, 3], [6, -1]], np.int32)  # runs: not in it
    holed = np.array([[5, -1, 7]], np.int32)  # a hole: not in it
    order = observe_entity_order(
        [_bucket_of(loose), _bucket_of(grouped), _bucket_of(holed)], 10
    )
    assert isinstance(order, EntityOrder) and order.keys.dtype == np.int32
    # rows 4, 1, 0 in slot order; the rest after them, in row order
    np.testing.assert_array_equal(order.keys, [2, 1, 3, 4, 0, 5, 6, 7, 8, 9])
    runs = order.runs[0]
    np.testing.assert_array_equal(runs.starts, [0, 2, 0])
    np.testing.assert_array_equal(runs.counts, [2, 1, 0])
    assert order.runs[1] is None and order.runs[2] is None
    # a row held twice is no permutation: no order
    twice = np.array([[4, 8], [1, -1]], np.int32)
    assert observe_entity_order(
        [_bucket_of(loose), _bucket_of(twice)], 10
    ) is None
    # nothing without runs: no order
    assert observe_entity_order([_bucket_of(grouped)], 10) is None


def _shuffled_codes(counts, seed=0):
    return np.random.default_rng(seed).permutation(
        np.repeat(np.arange(len(counts)), counts)
    )


def _gather_groups(groups, residual):
    """The element gather of every group's slots, on the host."""
    return [
        np.stack([_gathered(residual, b.bucket.row_index) for b in members])
        if len(members) > 1
        else _gathered(residual, members[0].bucket.row_index)
        for members in groups
    ]


SORTED_CASES = {
    # name: (rows an entity, reservoir cap, sub-blocks of the widest class)
    "one_group_a_class_padding_slots": (
        [3, 5, 7, 2, 6, 1, 4, 8, 5, 3, 7, 6], None, 1
    ),
    "folded_group_an_entity_with_no_real_slot": (
        [5] * 11 + [2, 3], None, 3
    ),
    "rows_held_by_no_block": ([12] * 10 + [3, 20, 9], 8, 1),
    "capacities_over_and_under_128": ([200, 300, 130, 60, 90, 7, 1], None, 1),
}


@pytest.mark.parametrize("case", sorted(SORTED_CASES))
def test_the_sorted_path_equals_the_element_gather_bitwise(case):
    counts, cap, n_sub = SORTED_CASES[case]
    red = _build(_shuffled_codes(counts, seed=len(counts)), cap=cap, seed=7)
    n = red.row_entity_codes.shape[0]
    assert "entity_order" not in red.__dict__
    kw = {}
    if n_sub > 1:
        bucket = max(red.buckets, key=lambda b: b.num_entities)
        per_entity = 4 * bucket.capacity * (red.local_dim + bucket.capacity)
        kw["dense_bytes_budget"] = (
            per_entity * -(-bucket.num_entities // n_sub)
        )
    rng = np.random.default_rng(len(case))
    residual = rng.normal(size=n).astype(np.float32)
    residual[rng.integers(0, n, size=5)] = -0.0  # a sign a sum would lose
    name = f"sorted-{case}"
    before = _slots_counted(name)
    groups, got = _problem(**kw).group_offsets(
        red, jnp.asarray(residual), coordinate=name
    )
    windows, sorted_, slots = (
        a - b for a, b in zip(_slots_counted(name), before)
    )
    read = sum(
        sum(b.num_real for b in members) * members[0].bucket.capacity
        for members in groups
    )
    # only an entity of ONE row is a run in a shuffled table
    assert slots == 0 and windows + sorted_ == read and sorted_ > 0
    keys = red.entity_order.keys
    assert sorted(keys.tolist()) == list(range(n))
    for have, want in zip(got, _gather_groups(groups, residual)):
        np.testing.assert_array_equal(_bits(have), _bits(want))
    if n_sub > 1:
        assert max(len(members) for members in groups) == n_sub
        assert any(
            b.num_real < b.bucket.num_entities for m in groups for b in m
        )
    if cap is not None:
        assert red.num_passive_rows > 0
        held = sum(int((b.row_index >= 0).sum()) for b in red.buckets)
        free = np.ones(n, bool)
        for b in red.buckets:
            free[b.row_index[b.row_index >= 0]] = False
        # rows held by no block sort after the last held one, in row order
        np.testing.assert_array_equal(
            keys[free], held + np.arange(free.sum())
        )
    if "128" in case:
        assert {b.capacity for b in red.buckets} >= {64, 256, 512}


def test_the_three_paths_count_every_slot_once_and_upload_their_own():
    """Windows for a grouped class, the sorted windows for a shuffled one,
    the gather for a shuffled one with a hole among its real slots (no
    run in the order): the three counts sum to every slot read, the
    offsets are the gather's bits, and only the gathered bucket uploads
    its ``row_index``."""
    grouped = np.repeat(np.arange(20), 3)  # capacity 4, runs
    loose = _shuffled_codes([20] * 12 + [40] * 8, seed=1) + 20
    red = _build(np.concatenate([grouped, loose]))
    by_cap = {b.capacity: i for i, b in enumerate(red.buckets)}
    assert red.buckets[by_cap[4]].row_runs is not None
    buckets = list(red.buckets)
    holed = buckets[by_cap[64]]
    assert (holed.row_index < 0).any(axis=1).all()
    # each entity's padding slot first: a hole before its real slots
    buckets[by_cap[64]] = replace(
        holed, row_index=np.roll(holed.row_index, 1, axis=1)
    )
    red = replace(red, buckets=buckets)
    n = red.row_entity_codes.shape[0]
    residual = np.random.default_rng(4).normal(size=n).astype(np.float32)
    problem = _problem()
    before = _slots_counted("three")
    groups, got = problem.group_offsets(
        red, jnp.asarray(residual), coordinate="three"
    )
    counted = tuple(a - b for a, b in zip(_slots_counted("three"), before))
    size = {b.capacity: b.row_index.size for b in red.buckets}
    assert counted == (size[4], size[32], size[64])
    assert sum(counted) == sum(size.values())
    for have, want in zip(got, _gather_groups(groups, residual)):
        np.testing.assert_array_equal(_bits(have), _bits(want))
    uploaded = [what for _, what in problem._device_cache]
    assert uploaded.count("rows") == 1
    cache = red.__dict__["_residual_device_cache"]
    np.testing.assert_array_equal(
        np.asarray(cache["entity_order"]), red.entity_order.keys
    )
    assert red.entity_order.runs[by_cap[64]] is None


@pytest.mark.parametrize(
    "case", ["built", "updated", "updated_under_a_residual",
             "variances_under_a_residual"],
)
def test_the_entity_order_is_observed_only_where_the_residual_is_read(case):
    """A dataset observes its entity order the first time a replicated
    bank update reads the residual through it, and keeps it: a build, or
    an update with no residual (a streamed segment's), builds none."""
    red = _build(_shuffled_codes([5, 9, 3, 12, 7, 6], seed=3))
    n = red.row_entity_codes.shape[0]
    bank = jnp.zeros((red.num_entities, red.local_dim), jnp.float32)
    residual = jnp.asarray(
        np.random.default_rng(3).normal(size=n), jnp.float32
    )
    problem = _problem()
    if case == "updated":
        problem.update_bank(bank, red)
    elif case == "updated_under_a_residual":
        problem.update_bank(bank, red, residual_offsets=residual)
    elif case == "variances_under_a_residual":
        problem.bank_variances(bank, red, residual)
    read = case.endswith("under_a_residual")
    assert ("entity_order" in red.__dict__) == read
    if read:
        order = red.entity_order
        assert order is red.entity_order  # observed once
        assert sorted(order.keys.tolist()) == list(range(n))
