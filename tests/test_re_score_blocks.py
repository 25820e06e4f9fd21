"""The random effect scores from the blocks its solver holds
(game/random_effect.score_block, re_score; game/pod.pod_score): every
case against the element gather on the row view as the plain reference,
float32, to 1e-6 of the largest score. The passive rows of a replicated
bank are scored from per-entity chunks of a block's shape
(re_score_passive) where the bank is narrow enough for the compare. The
rows no dense block or chunk holds (a sparse block's, a view without
buckets, the entity mesh, a bank too wide) keep the gather, and
``photon_re_score_rows_total`` says how many rode which path. A block's
scores reach the row vector through the order the residual is read in
(windows of the row vector, of the entity order sorted back, or the
element scatter), each row equal to the element scatter's, and
``photon_re_score_placed_slots_total`` says which."""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.game.config import (
    ProjectorType,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.game.coordinate import (
    PodRandomEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.data import EntityIndex, GameDataset, ShardData
from photon_ml_tpu.game import random_effect
from photon_ml_tpu.game.pod import PodRandomEffectProblem, ShardedREBank
from photon_ml_tpu.game.random_effect import (
    RandomEffectOptimizationProblem,
    gather_scores,
    re_score,
    re_score_passive,
    score_block,
    score_blocks,
    score_places,
    score_plan,
    score_random_effect,
)
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.obs.registry import default_registry
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.optim.config import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.parallel.mesh import entity_mesh
from photon_ml_tpu.utils.index_map import IndexMap, feature_key

ATOL = 1e-6  # of the largest score


def _dataset(seed=0, n=431, E=23, d=40, k=6, cap=None,
             projector=ProjectorType.INDEX_MAP, codes=None,
             sparse_rows=True):
    """A GameDataset and its RandomEffectDataset: an uneven entity
    histogram (several capacity classes; ``codes``, where given, in its
    place), weight-0 rows (every 17th) and rows with no entity (every
    29th; ``sparse_rows`` False: neither, but those ``codes`` has),
    entries of value 0."""
    rng = np.random.default_rng(seed)
    if codes is None:
        codes = np.minimum(
            rng.geometric(0.12, size=n) - 1, E - 1
        ).astype(np.int32)
    n, E = len(codes), max(E, int(codes.max()) + 1)
    if sparse_rows:
        codes[::29] = -1
    ix = rng.integers(0, d, size=(n, k)).astype(np.int32)
    v = rng.normal(size=(n, k)).astype(np.float32)
    v[::7, -2:] = 0.0
    w = np.ones(n, np.float32)
    if sparse_rows:
        w[::17] = 0.0
    imap = IndexMap.build(
        (feature_key(f"f{i}", "") for i in range(d)), add_intercept=False
    )
    ds = GameDataset(
        uids=[str(i) for i in range(n)],
        labels=(rng.uniform(size=n) > 0.5).astype(np.float32),
        offsets=np.zeros(n, np.float32), weights=w,
        shards={"s": ShardData(
            indices=ix, values=v, index_map=imap, intercept_index=None
        )},
        entity_codes={"user": codes},
        entity_indexes={"user": EntityIndex.build(
            "user", [f"e{i:03d}" for i in range(E)]
        )},
        num_real_rows=n,
    )
    red = build_random_effect_dataset(ds, RandomEffectDataConfiguration(
        random_effect_type="user", feature_shard_id="s",
        projector_type=projector, active_data_upper_bound=cap,
    ))
    return ds, red


def _problem(**kw):
    kw.setdefault("reg_weight", 0.5)
    return RandomEffectOptimizationProblem(
        LOGISTIC, OptimizerConfig(max_iter=5),
        RegularizationContext(RegularizationType.L2), **kw
    )


def _bank(red, seed=1):
    rng = np.random.default_rng(seed)
    return rng.normal(
        size=(red.num_entities, red.local_dim)
    ).astype(np.float32)


def _reference(bank, red):
    """The gather on the row view, in numpy float32."""
    codes = red.row_entity_codes
    rows = bank[np.maximum(codes, 0)]
    looked = np.take_along_axis(rows, red.row_local_indices, axis=1)
    score = np.sum(red.row_local_values * looked, axis=-1, dtype=np.float32)
    return np.where(codes >= 0, score, np.float32(0.0))


def _close(got, want):
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.max(np.abs(got - want)) <= ATOL * np.max(np.abs(want))
    # a row with no entity, or of weight 0, scores exactly 0
    assert not got[want == 0].any()


def _counted(coordinate):
    counter = default_registry().counter("photon_re_score_rows_total")
    return tuple(
        counter.value(coordinate=coordinate, path=path)
        for path in ("blocks", "chunks", "gather")
    )


def _split_dataset(n_sub, cap):
    """A dataset whose largest bucket does not divide into ``n_sub``
    equal sub-blocks, and the ``dense_bytes_budget`` under which it runs
    as that many, the last one padded."""
    for seed in range(20):
        _, red = _dataset(seed=seed, n=900, E=61, cap=cap)
        bucket = max(red.buckets, key=lambda b: b.num_entities)
        if bucket.num_entities % n_sub:
            break
    per_entity = 4 * bucket.capacity * (red.local_dim + bucket.capacity)
    return red, per_entity * -(-bucket.num_entities // n_sub)


CASES = {
    # name: (dataset arguments, problem arguments, kernel the plan says)
    "every_row_active": ({}, {}, "blocks"),
    "passive_rows": ({"cap": 8}, {}, "blocks+chunks"),
    # a bank past the compare's width (the limit patched down): the
    # passive rows keep the flat gather, as every other row does
    "passive_rows_too_wide": ({"cap": 8}, {}, "gather"),
    "identity_projector": (
        {"projector": ProjectorType.IDENTITY}, {}, "blocks"
    ),
    "variances_unfolded": ({}, {"compute_variances": True}, "blocks"),
    "sparse_layout": ({}, {"layout": "sparse"}, "gather"),
    # past the width where the compare still beats the gather
    "too_wide_to_compare": (
        {"projector": ProjectorType.IDENTITY, "d": 16385}, {}, "gather"
    ),
}


def _passive_rows(red):
    held = np.zeros(red.row_entity_codes.shape, bool)
    for b in red.buckets:
        held[b.row_index[b.row_index >= 0]] = True
    return np.nonzero((red.row_entity_codes >= 0) & ~held)[0]


def _check_chunks(red, plan):
    """The passive rows' chunks: ``[C, S, ...]`` at the widest bucket's
    capacity, every passive row in exactly one slot, a chunk's rows its
    entity's, and the padding slots on no row with values 0."""
    rows, codes, ix, v = (np.asarray(a) for a in plan.passive)
    S = max(b.capacity for b in red.buckets)
    k = red.row_local_indices.shape[1]
    C = codes.shape[0]
    assert rows.shape == (C, S) and ix.shape == v.shape == (C, S, k)
    held = rows[rows >= 0]
    passive = _passive_rows(red)
    assert len(held) == len(set(held.tolist())) == len(passive)
    assert set(held.tolist()) == set(passive.tolist())
    on = rows >= 0
    assert np.all(red.row_entity_codes[np.maximum(rows, 0)][on]
                  == np.broadcast_to(codes[:, None], rows.shape)[on])
    np.testing.assert_array_equal(ix[on], red.row_local_indices[rows[on]])
    np.testing.assert_array_equal(v[on], red.row_local_values[rows[on]])
    assert not v[~on].any()
    # an entity's padding is under one chunk
    assert plan.passive_chunks == C
    assert plan.passive_padding == rows.size - len(passive)
    per = np.bincount(codes, minlength=red.num_entities)
    want = -(-np.bincount(red.row_entity_codes[passive],
                          minlength=red.num_entities) // S)
    np.testing.assert_array_equal(per, want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_blocks_match_the_gather(case, monkeypatch):
    data_kw, problem_kw, kernel = CASES[case]
    _, red = _dataset(**data_kw)
    assert len(red.buckets) >= 2  # two capacity classes and more
    if case == "passive_rows_too_wide":
        monkeypatch.setattr(
            random_effect, "_SCORE_BLOCK_MAX_DIM", red.local_dim - 1
        )
    problem, bank = _problem(**problem_kw), _bank(red)
    plan = score_plan(red, problem)
    assert plan.kernel == kernel
    valid = int(np.count_nonzero(red.row_entity_codes >= 0))
    assert plan.block_rows + plan.chunk_rows + plan.gather_rows == valid
    if kernel == "blocks":
        assert plan.rest is None and plan.block_rows == red.num_active_rows
    if case == "passive_rows":
        assert red.num_passive_rows > 0
        assert plan.block_rows == red.num_active_rows
        assert plan.chunk_rows == red.num_passive_rows
        assert plan.gather_rows == 0 and plan.rest is None
        _check_chunks(red, plan)
    if case == "passive_rows_too_wide":
        assert plan.chunk_rows == 0 and plan.gather_rows == valid
        assert plan.passive[0].shape == (red.num_passive_rows,)
        assert plan.rest[0].shape == (red.num_active_rows,)
    _close(score_random_effect(jnp.asarray(bank), red, problem),
           _reference(bank, red))


def _one_entity_codes(n=150, E=23, over=41):
    """Every entity under the cap of 8 but entity 0, which holds
    ``over`` rows."""
    codes = np.concatenate([
        np.zeros(over, np.int32),
        1 + np.arange(n - over, dtype=np.int32) % (E - 1),
    ])
    return np.random.default_rng(0).permutation(codes)


PASSIVE_CASES = {
    # name: (dataset arguments, bank, split into sub-blocks)
    "uneven_entities": ({"cap": 8}, "normal", False),
    "zero_bank": ({"cap": 8}, "zeros", False),
    "one_entity": (
        {"cap": 8, "n": 150, "codes": _one_entity_codes()}, "normal", False
    ),
    # the compare's live arrays over the budget: scanned sub-blocks
    "sub_blocks": ({"cap": 8}, "normal", True),
}


def _uneven_split_budget(red, plan):
    """A ``dense_bytes_budget`` that splits ``plan``'s chunks into a
    number of sub-blocks that does not divide them, and that number."""
    C, S, k = plan.passive[2].shape
    n_sub = next(n for n in range(2, C) if C % n)
    live = 4 * C * (red.local_dim + S * k)
    return -(-live // n_sub), n_sub


@pytest.mark.parametrize("case", sorted(PASSIVE_CASES))
def test_passive_chunks_score_as_the_gather(case):
    """``re_score_passive`` on the chunks against the element gather on
    the flat passive rows (the path a bank too wide keeps), float32, and
    every other row of the vector left as it was."""
    data_kw, which, split = PASSIVE_CASES[case]
    _, red = _dataset(**data_kw)
    plan = score_plan(red, _problem())
    assert plan.kernel == "blocks+chunks"
    _check_chunks(red, plan)
    if split:
        budget, n_sub = _uneven_split_budget(red, plan)
        plan = score_plan(red, _problem(dense_bytes_budget=budget))
        rows, codes = (np.asarray(a) for a in plan.passive[:2])
        assert rows.ndim == 3 and rows.shape[0] == n_sub
        # the last sub-block padded with chunks of an entity past the bank
        assert (codes[-1] == red.num_entities).any()
        assert not (rows[codes == red.num_entities] >= 0).any()
    passive = _passive_rows(red)
    counts = np.bincount(red.row_entity_codes[passive])
    S = max(b.capacity for b in red.buckets)
    # an entity whose passive rows fill no whole number of chunks
    assert np.any(counts % S)
    if case == "one_entity":
        assert np.count_nonzero(counts) == 1
    # rows with entries of value 0 among the passive rows
    assert (red.row_local_values[passive] == 0).any(axis=1).any()
    bank = _bank(red)
    if which == "zeros":
        bank = np.zeros_like(bank)
    before = np.full(red.row_entity_codes.shape, -7.0, np.float32)
    got = np.asarray(re_score_passive(
        jnp.asarray(before), jnp.asarray(bank), *plan.passive
    ))
    want = before.copy()
    want[passive] = gather_scores(
        jnp.asarray(bank), jnp.asarray(red.row_entity_codes[passive]),
        jnp.asarray(red.row_local_indices[passive]),
        jnp.asarray(red.row_local_values[passive]),
    )
    assert got.dtype == np.float32
    np.testing.assert_allclose(
        got, want, rtol=1e-6, atol=ATOL * np.max(np.abs(want[passive]))
    )
    if which == "zeros":
        assert not got[passive].any()


@pytest.mark.parametrize("n_sub", [2, 3])
@pytest.mark.parametrize("cap", [None, 8])
def test_a_split_bucket_scores_sub_block_by_sub_block(n_sub, cap):
    red, budget = _split_dataset(n_sub, cap)
    problem = _problem(dense_bytes_budget=budget)
    plan = score_plan(red, problem)
    largest = max(
        range(len(red.buckets)), key=lambda i: red.buckets[i].num_entities
    )
    (group,) = [g for g in plan.groups if g[0].bucket_index == largest]
    assert len(group) == n_sub  # one stacked group, scanned
    assert group[-1].num_real < group[-1].bucket.num_entities
    bank = _bank(red)
    _close(score_random_effect(jnp.asarray(bank), red, problem),
           _reference(bank, red))
    # the update's own stacked arrays: scoring uploaded no second copy
    stacked = red.__dict__["_stacked_device_cache"]
    before = set(stacked)
    problem.update_bank(
        jnp.asarray(bank), red,
        residual_offsets=jnp.zeros((red.row_entity_codes.shape[0],)),
    )
    assert set(stacked) == before


def _identity_view(base, x_lat):
    """``base``'s rows as dense identity-local features ``x_lat`` [n, L]
    (every bucket's indices the tiled ``arange(L)``): the identity blocks
    a values override's solvers run."""
    L = x_lat.shape[1]
    n = base.row_local_indices.shape[0]
    buckets = []
    for b in base.buckets:
        ok = (b.row_index >= 0)[:, :, None]
        buckets.append(replace(
            b,
            indices=np.broadcast_to(
                np.arange(L, dtype=np.int32), b.row_index.shape + (L,)
            ).copy(),
            values=np.where(ok, x_lat[np.maximum(b.row_index, 0)], 0.0)
            .astype(np.float32),
            identity_indices=True,
        ))
    return replace(
        base, local_dim=L,
        projection=np.tile(np.arange(L, dtype=np.int32), (base.num_entities, 1)),
        row_local_indices=np.tile(np.arange(L, dtype=np.int32), (n, 1)),
        row_local_values=x_lat, buckets=buckets, random_projection=None,
    )


def test_an_identity_indices_bucket_multiplies_with_no_compare():
    _, red = _dataset(projector=ProjectorType.IDENTITY)
    rng = np.random.default_rng(3)
    x_lat = rng.normal(
        size=(red.row_entity_codes.shape[0], 5)
    ).astype(np.float32)
    view = _identity_view(red, x_lat)
    assert all(b.identity_indices for b in view.buckets)
    problem, bank = _problem(), _bank(view)
    assert score_plan(view, problem).kernel == "blocks"
    _close(score_random_effect(jnp.asarray(bank), view, problem),
           _reference(bank, view))
    # and the lookup itself, both ways, on one block
    b = view.buckets[0]
    w = jnp.asarray(bank[b.entity_codes])
    ix, v = jnp.asarray(b.indices), jnp.asarray(b.values)
    np.testing.assert_allclose(
        score_block(w, ix, v, True), score_block(w, ix, v, False),
        rtol=0, atol=ATOL * float(jnp.max(jnp.abs(w))) * 5,
    )


@pytest.mark.parametrize("how", ["no_buckets", "no_problem", "entity_mesh"])
def test_who_holds_no_block_or_no_problem(how):
    _, red = _dataset()
    bank = _bank(red)
    if how == "no_buckets":  # the driver's validation view, score_rows
        red = replace(red, buckets=[])
        plan = score_plan(red)
        assert plan.kernel == "gather" and plan.rest[0] is None
        got = score_random_effect(jnp.asarray(bank), red)
    elif how == "no_problem":  # RandomEffectModel.score: default budget
        assert score_plan(red).kernel == "blocks"
        got = score_random_effect(jnp.asarray(bank), red)
    else:  # its blocks are entity-sharded: the gather stays
        problem = _problem(mesh=entity_mesh(2))
        assert score_plan(red, problem).kernel == "gather"
        got = score_random_effect(jnp.asarray(bank), red, problem)
    _close(got, _reference(bank, red))


@pytest.mark.parametrize("cap", [None, 8])
def test_float32_exact_under_a_bfloat16_default_precision(cap):
    _, red = _dataset(cap=cap)
    problem, bank = _problem(), _bank(red)
    with jax.default_matmul_precision("bfloat16"):
        got = score_random_effect(jnp.asarray(bank), red, problem)
    _close(got, _reference(bank, red))


@pytest.mark.parametrize("cap", [None, 8])
def test_the_coordinate_counts_rows_by_path_and_names_the_kernel(cap):
    ds, red = _dataset(cap=cap)
    name = f"per-user-cap-{cap}"
    coord = RandomEffectCoordinate(name, ds, red, _problem())
    model = replace(coord.initialize_model(), bank=jnp.asarray(_bank(red)))
    before = _counted(name)
    _close(coord.score(model), _reference(np.asarray(model.bank), red))
    blocks, chunks, gather = (a - b for a, b in zip(_counted(name), before))
    assert (blocks, chunks, gather) == (
        red.num_active_rows, red.num_passive_rows, 0
    )
    assert coord.score_kernel == ("blocks+chunks" if cap else "blocks")
    plan = score_plan(red, coord.problem)
    _, _, paths = score_places(coord.problem, red, plan)
    placed = [p for p in ("windows", "sorted", "slots")
              if any(q == p for q, _ in paths)]
    assert coord.score_attrs == {"placed": "+".join(placed), **({
        "passive_chunks": plan.passive_chunks,
        "passive_padding": plan.passive_padding,
    } if cap else {})}


def test_the_program_keeps_its_module_name():
    _, red = _dataset(cap=8)
    problem = _problem()
    plan = score_plan(red, problem)
    blocks = []
    for members in plan.groups:
        ix, v, _, _, _, codes = problem._bucket_device_args(
            members[0].bucket
        )
        blocks.append(
            (codes, ix, v, problem._bucket_rows(members[0].bucket))
        )
    lowered = re_score.lower(
        jnp.asarray(_bank(red)), tuple(blocks), plan.rest,
        identity=(False,) * len(blocks),
        num_rows=red.row_entity_codes.shape[0],
        paths=(("slots", 0),) * len(blocks),
    )
    assert "module @jit_re_score" in lowered.as_text()


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("cap", [None, 8])
def test_the_pod_scores_its_blocks_like_the_replicated_bank(n_dev, cap):
    ds, red = _dataset(n=700, E=37, cap=cap)
    mesh = entity_mesh(n_dev)
    base, bank = _problem(), _bank(red)
    pod = PodRandomEffectProblem(base, mesh)
    view = pod.pod_view(red)
    assert view.score_kernel == ("blocks+gather" if cap else "blocks")
    assert view.score_block_rows == red.num_active_rows
    assert view.score_gather_rows == red.num_passive_rows
    sharded = ShardedREBank.from_global(mesh, pod.spec_for(red), bank)
    got = pod.score(sharded, red)
    _close(got, _reference(bank, red))
    _close(got, np.asarray(score_random_effect(jnp.asarray(bank), red, base)))
    # the coordinate counts what the view says
    name = f"pod-{n_dev}-{cap}"
    coord = PodRandomEffectCoordinate(name, ds, red, base, mesh=mesh)
    model = coord.initialize_model()
    before = _counted(name)
    assert not np.asarray(coord.score(model)).any()  # the zero bank
    assert tuple(
        a - b for a, b in zip(_counted(name), before)
    ) == (red.num_active_rows, 0, red.num_passive_rows)


def test_a_pod_block_left_to_the_sparse_solver_keeps_the_gather():
    _, red = _dataset(n=700, E=37)
    mesh = entity_mesh(2)
    base, bank = _problem(layout="sparse"), _bank(red)
    pod = PodRandomEffectProblem(base, mesh)
    view = pod.pod_view(red)
    assert view.score_kernel == "gather" and view._score_rest[0] is None
    sharded = ShardedREBank.from_global(mesh, pod.spec_for(red), bank)
    _close(pod.score(sharded, red), _reference(bank, red))


# ---- where a block's scores reach the row vector -----------------------


def _entity_rows(counts, *, grouped, seed=0):
    """Codes of rows holding ``counts[e]`` rows of entity e: grouped by
    entity (each entity's rows a run, the entities in a shuffled order,
    a row with no entity after every third), or shuffled whole, with
    rows of no entity among them."""
    rng = np.random.default_rng(seed)
    if not grouped:
        codes = np.repeat(np.arange(len(counts)), counts)
        codes = np.concatenate([codes, np.full(len(counts) // 3, -1)])
        return rng.permutation(codes).astype(np.int32)
    runs = []
    for i, e in enumerate(rng.permutation(len(counts))):
        runs.append(np.full(counts[e], e))
        if i % 3 == 2:
            runs.append(np.array([-1]))
    return np.concatenate(runs).astype(np.int32)


def _with_a_hole(red, capacity):
    """``red`` with the bucket of ``capacity``'s slots turned by one, so
    that each entity's padding slot comes before its real ones: no run
    in the row vector or in the entity order (its scores keep the
    element scatter)."""
    buckets = []
    for b in red.buckets:
        if b.capacity == capacity:
            assert (b.row_index < 0).any(axis=1).all()
            b = replace(b, row_runs=None, **{
                name: np.roll(getattr(b, name), 1, axis=1)
                for name in ("row_index", "indices", "values", "labels",
                             "offsets", "weights")
            })
        buckets.append(b)
    return replace(red, buckets=buckets)


# 1, 128 and over 128 rows an entity among them: capacities 1 to 512,
# three entities in the widest class
_COUNTS = [1, 1, 3, 128, 5, 300, 7, 1, 260, 2, 60, 9, 4, 100, 1, 33, 6, 400]

PLACE_CASES = {
    # name: (rows grouped by entity, weight-0 rows and rows of no entity
    #        every 17th and 29th, reservoir cap, widest class folded into
    #        two sub-blocks, a bucket turned to have a hole, the bank,
    #        the paths the groups take). An entity of one row is a run
    #        in any table.
    "grouped": (True, False, None, False, None, "normal", {"windows"}),
    "shuffled": (
        False, False, None, False, None, "normal", {"windows", "sorted"}
    ),
    "grouped_folded": (True, False, None, True, None, "normal", {"windows"}),
    "shuffled_folded": (
        False, False, None, True, None, "normal", {"windows", "sorted"}
    ),
    # a weight-0 row or a capped entity's left-out row is no bucket's and
    # breaks its entity's run: its bucket reads the entity order, and the
    # row rides ``rest``
    "grouped_sparse_rows_capped": (
        True, True, 64, False, None, "normal", {"windows", "sorted"}
    ),
    "shuffled_sparse_rows_capped": (
        False, True, 64, False, None, "normal", {"windows", "sorted"}
    ),
    "three_paths": (
        True, False, None, False, 8, "normal", {"windows", "slots"}
    ),
    "three_paths_shuffled": (
        False, False, None, False, 8, "normal", {"windows", "sorted", "slots"}
    ),
    "zero_bank": (
        False, True, 64, False, None, "zeros", {"windows", "sorted"}
    ),
    # a diverged entity's row: its padding slots score NaN (0 x inf),
    # which stays off the rows its run shares a lane row with
    "an_infinite_bank_row": (
        False, True, 64, False, None, "inf", {"windows", "sorted"}
    ),
}


@pytest.mark.parametrize("case", sorted(PLACE_CASES))
def test_every_path_places_the_element_scatters_scores(case):
    """``re_score`` on the paths the data gives (``windows``, ``sorted``,
    ``slots``) against the same program with every group on the element
    scatter, and against the gather on the row view: equal row for row
    (``==``, so that the sign of a zero is all that may differ), rows of
    no entity and rows no bucket holds among them."""
    grouped, sparse, cap, folded, holed, which, want_paths = (
        PLACE_CASES[case]
    )
    codes = _entity_rows(_COUNTS, grouped=grouped, seed=len(case))
    # one entry a row: a row's score is one product on every path, so the
    # gather's is the blocks' to the bit (the sum over k takes another
    # order in the two programs, ulps apart)
    _, red = _dataset(codes=codes, k=1, cap=cap, sparse_rows=sparse)
    if holed is not None:
        red = _with_a_hole(red, holed)
    kw = {}
    if folded:  # the widest class's three entities as two sub-blocks of
        capacity = max(b.capacity for b in red.buckets)  # the primal kind
        d = red.local_dim
        kw["dense_bytes_budget"] = 2 * 4 * (capacity * d + d * d)
    problem = _problem(**kw)
    plan = score_plan(red, problem, passive_apart=False)
    capacities = {m[0].bucket.capacity for m in plan.groups}
    assert capacities >= ({1, 64} if cap else {1, 128, 512})
    if folded:  # a folded group, its last sub-block's padding entity on
        widest = max(plan.groups, key=len)  # no slot at all
        assert len(widest) == 2 and widest[0].bucket.capacity == 512
        assert widest[-1].num_real < widest[-1].bucket.num_entities
    if cap is not None:
        assert red.num_passive_rows > 0 and plan.rest is not None
    specs, order, paths = score_places(problem, red, plan)
    assert {path for path, _ in paths} == want_paths
    assert (order is not None) == ("sorted" in want_paths)
    bank = np.zeros_like(_bank(red)) if which == "zeros" else _bank(red)
    if which == "inf":  # an entity of 5 to 8 rows: 3 to 0 padding slots
        (of_8,) = [b for b in red.buckets if b.capacity == 8]
        bank[of_8.entity_codes[0]] = np.inf
    bank = jnp.asarray(bank)
    identity = tuple(m[0].bucket.identity_indices for m in plan.groups)
    n = red.row_entity_codes.shape[0]
    got = np.asarray(re_score(
        bank, score_blocks(problem, red, plan, specs), plan.rest, order,
        identity=identity, num_rows=n, paths=paths,
    ))
    scattered = np.asarray(re_score(
        bank, score_blocks(problem, red, plan), plan.rest,
        identity=identity, num_rows=n, paths=(("slots", 0),) * len(paths),
    ))
    codes = red.row_entity_codes
    gathered = np.where(codes >= 0, np.asarray(gather_scores(
        bank, jnp.asarray(np.maximum(codes, 0)),
        jnp.asarray(red.row_local_indices), jnp.asarray(red.row_local_values),
    )), np.float32(0.0))
    assert got.dtype == np.float32 and got.shape == (n,)
    # ``==`` row for row, a NaN where the other has one
    np.testing.assert_array_equal(got, scattered)
    np.testing.assert_array_equal(got, gathered)
    assert not got[codes < 0].any()
    if which == "zeros":
        assert not got.any()
    else:
        assert (got != 0).sum() > n // 2
    if which == "inf":
        assert 0 < (~np.isfinite(got)).sum() <= 8
    # the whole pass, the passive rows on their chunks, as the gather
    np.testing.assert_array_equal(
        score_random_effect(bank, red, problem), gathered
    )


def test_the_coordinate_counts_placed_slots_by_path_and_names_them():
    """``photon_re_score_placed_slots_total`` counts a block's real
    entities times its capacity, once a pass, under the path its scores
    took, and ``cd.score``'s ``placed`` names those paths."""
    codes = _entity_rows(_COUNTS, grouped=True, seed=5)
    ds, red = _dataset(codes=codes, cap=64, sparse_rows=False)
    red = _with_a_hole(red, 8)
    coord = RandomEffectCoordinate("placed", ds, red, _problem())
    counter = default_registry().counter(
        "photon_re_score_placed_slots_total"
    )

    def counted():
        return {
            path: counter.value(coordinate="placed", path=path)
            for path in ("windows", "sorted", "slots")
        }

    plan = score_plan(red, coord.problem)
    want = {"windows": 0, "sorted": 0, "slots": 0}
    for members in plan.groups:
        bucket = members[0].bucket
        path = "slots" if bucket.capacity == 8 else (
            "windows" if bucket.row_runs is not None else "sorted"
        )
        want[path] += sum(b.num_real for b in members) * bucket.capacity
    assert want["windows"] and want["sorted"] and want["slots"]
    before = counted()
    model = replace(coord.initialize_model(), bank=jnp.asarray(_bank(red)))
    coord.score(model)
    coord.score(model)
    after = counted()
    assert {p: after[p] - before[p] for p in want} == {
        p: 2 * slots for p, slots in want.items()
    }
    assert coord.score_attrs["placed"] == "windows+sorted+slots"
