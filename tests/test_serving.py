"""Online scoring service tests (ISSUES 7+8): scoring parity with the
batch driver (bitwise), micro-batch demux under concurrent submitters,
hot-swap parity + rollback, padded-shape ladder selection, the
zero-recompile / one-readback-per-dispatch contract, and the
serving-under-fire layer — admission control (shed/deadline), graceful
FE-only degradation, and bounded shutdown (every future exactly one
terminal outcome under clean close, drain, and a KILL fault plan).
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

from photon_ml_tpu.game.config import FeatureShardConfiguration
from photon_ml_tpu.game.data import build_game_dataset
from photon_ml_tpu.game.model_io import LoadedGameModel
from photon_ml_tpu.parallel import overlap
from photon_ml_tpu.serving import (
    AdmissionController,
    BatcherClosed,
    DeadlineExceeded,
    DrainTimeout,
    EntityRowIndex,
    MicroBatcher,
    RequestShed,
    ScoreOutcome,
    ServingMetrics,
    ServingModel,
    ServingPrograms,
    build_model_bank,
    request_from_record,
    requests_from_dataset,
    select_shape,
)
from photon_ml_tpu.task import TaskType

SHARDS = [
    FeatureShardConfiguration("g", ["features"]),
    FeatureShardConfiguration("u", ["userFeatures"]),
]


def synth_records(rng, n=60, n_users=7, d_g=5, d_u=3):
    recs = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        recs.append({
            "uid": f"r{i}",
            "response": float(rng.integers(0, 2)),
            "offset": float(rng.normal() * 0.1),
            "weight": float(rng.uniform(0.5, 2.0)),
            "metadataMap": {"userId": f"user{u}"},
            "features": [
                {"name": f"g{j}", "term": "", "value": float(rng.normal())}
                for j in range(d_g)
            ],
            "userFeatures": [
                {"name": f"u{j}", "term": "", "value": float(rng.normal())}
                for j in range(d_u)
            ],
        })
    return recs


def synth_model(rng, n_users=7, d_g=5, d_u=3, *, scale=1.0, drop_user=True):
    """A LoadedGameModel with one FE + one per-user RE coordinate; one
    user deliberately has NO model (the unknown-entity path)."""
    lm = LoadedGameModel()
    lm.fixed_effects["global"] = (
        "g",
        {f"g{j}\t": float(rng.normal()) * scale for j in range(d_g)},
    )
    users = range(n_users - 1) if drop_user else range(n_users)
    lm.random_effects["per-user"] = (
        "userId",
        "u",
        {
            f"user{e}": {
                f"u{j}\t": float(rng.normal()) * scale for j in range(d_u)
            }
            for e in users
        },
    )
    return lm


def batch_reference_scores(lm, ds):
    """What the batch scoring driver writes: raw scores + offsets."""
    return np.asarray(
        lm.score(ds, TaskType.LOGISTIC_REGRESSION) + jnp.asarray(ds.offsets)
    )[: ds.num_real_rows]


def make_bank(lm, ds, **kw):
    imaps = {sid: sd.index_map for sid, sd in ds.shards.items()}
    widths = {sid: sd.indices.shape[1] for sid, sd in ds.shards.items()}
    return build_model_bank(lm, imaps, widths, **kw)


@pytest.fixture
def served(rng):
    recs = synth_records(rng)
    ds = build_game_dataset(recs, SHARDS, ["userId"])
    lm = synth_model(rng)
    bank = make_bank(lm, ds)
    programs = ServingPrograms((1, 8, 64))
    programs.ensure_compiled(bank)
    return recs, ds, lm, bank, programs


class TestScoringParity:
    def test_serving_scores_bitwise_match_batch_scorer(self, served):
        """The acceptance bar: the request path reproduces the batch
        scoring driver's scores BITWISE, including offsets, masked
        unknown entities, and weights-irrelevance."""
        _, ds, lm, bank, programs = served
        ref = batch_reference_scores(lm, ds)
        metrics = ServingMetrics()
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            futs = [mb.submit(r) for r in requests_from_dataset(ds, bank)]
            got = np.asarray([f.result() for f in futs], np.float32)
        assert np.array_equal(got, ref)

    def test_single_request_dispatches_shape_one(self, served):
        _, ds, lm, bank, programs = served
        ref = batch_reference_scores(lm, ds)
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            for i in (0, 7, 23):
                assert mb.score(reqs[i]) == ref[i]
        snap = metrics.snapshot()
        assert snap["shape_counts"] == {"1": 3}
        assert snap["pad_waste_frac"] == 0.0

    def test_unknown_entity_scores_through_fe_only(self, served):
        """A request whose entity the model never saw gets code -1 and
        scores 0 through the RE coordinate — exactly the batch scorer's
        masked-code semantics (synth_model drops the last user)."""
        recs, ds, lm, bank, _ = served
        missing = f"user{6}"
        assert any(
            r["metadataMap"]["userId"] == missing for r in recs
        ), "fixture must exercise the unknown entity"
        assert bank.entity_row("userId", missing) == -1
        assert bank.entity_row("userId", "user0") >= 0

    def test_fe_only_model_under_multi_shard_config(self, served):
        """An FE-only model served with a multi-shard request config:
        requests carry features for shards the spec never scores — the
        batch must assemble (and the AOT program run) on exactly the
        spec's shards, scoring bitwise the FE-only batch path."""
        _, ds, lm, _bank, _ = served
        fe = LoadedGameModel()
        fe.fixed_effects = dict(lm.fixed_effects)
        bank = make_bank(fe, ds)  # widths cover BOTH shards
        assert set(bank.shard_widths) == {"g", "u"}
        assert bank.used_shards == ("g",)
        programs = ServingPrograms((1, 8))
        programs.ensure_compiled(bank)
        ref = batch_reference_scores(fe, ds)
        with MicroBatcher(lambda: bank, programs) as mb:
            got = np.asarray(
                [mb.score(r) for r in requests_from_dataset(ds, bank)],
                np.float32,
            )
        assert np.array_equal(got, ref)

    def test_record_assembly_matches_dataset_assembly(self, served):
        """The stdin path (request_from_record through index maps) and
        the Avro replay path (requests_from_dataset) produce identical
        scores for the same logical record."""
        recs, ds, lm, bank, programs = served
        ref = batch_reference_scores(lm, ds)
        with MicroBatcher(lambda: bank, programs) as mb:
            for i in (0, 11, 42):
                req = request_from_record(recs[i], bank, SHARDS)
                assert mb.score(req) == ref[i]

    def test_record_width_overflow_raises(self, served):
        recs, ds, lm, bank, _ = served
        fat = dict(recs[0])
        fat["features"] = [
            {"name": f"g{j % 5}", "term": "", "value": 1.0}
            for j in range(bank.shard_widths["g"] + 1)
        ]
        with pytest.raises(ValueError, match="exceeds shard"):
            request_from_record(fat, bank, SHARDS)

    def test_record_missing_id_omits_metadata_and_scores_fe_only(
        self, served
    ):
        """A record with no resolvable entity id scores FE-only (same as
        an unknown entity) and its metadataMap OMITS the key — never the
        literal string "None" — matching the dataset path's records."""
        recs, ds, lm, bank, programs = served
        bare = dict(recs[0])
        bare.pop("metadataMap")
        req = request_from_record(bare, bank, SHARDS)
        assert req.entity_ids == {"userId": None}
        assert req.metadata is None
        unknown = dict(recs[0])
        unknown["metadataMap"] = {"userId": "no-such-user"}
        req_unknown = request_from_record(unknown, bank, SHARDS)
        assert req_unknown.metadata == {"userId": "no-such-user"}
        with MicroBatcher(lambda: bank, programs) as mb:
            assert mb.score(req) == mb.score(req_unknown)


class TestEntityRowIndex:
    def test_dict_backend(self):
        idx = EntityRowIndex(["a", "b", "c"])
        assert idx.backend == "dict"
        assert [idx.row_of(e) for e in ("a", "c", "zz")] == [0, 2, -1]
        assert idx.rows_of(["b", "nope", "a"]).tolist() == [1, -1, 0]

    def test_native_backend_matches_dict(self):
        ids = [f"member-{i}" for i in range(257)]
        try:
            native = EntityRowIndex(ids, native_threshold=1)
        except Exception:
            pytest.skip("native toolchain unavailable")
        if native.backend != "native":
            pytest.skip("native store fell back")
        plain = EntityRowIndex(ids)
        probe = ids[::13] + ["member-9999", ""]
        assert native.rows_of(probe).tolist() == plain.rows_of(probe).tolist()


class TestLadder:
    def test_select_shape_picks_smallest_fit(self):
        ladder = (1, 8, 64, 256)
        assert select_shape(1, ladder) == 1
        assert select_shape(2, ladder) == 8
        assert select_shape(8, ladder) == 8
        assert select_shape(65, ladder) == 256
        with pytest.raises(ValueError):
            select_shape(257, ladder)

    def test_bad_ladder_rejected(self):
        with pytest.raises(ValueError):
            ServingPrograms((8, 1))
        with pytest.raises(ValueError):
            ServingPrograms(())

    def test_coalesced_batches_use_ladder_shapes(self, served):
        """Submitting a burst while the dispatcher is busy coalesces the
        backlog into the smallest fitting padded shape."""
        _, ds, lm, bank, programs = served
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            futs = [mb.submit(r) for r in reqs]
            for f in futs:
                f.result()
        snap = metrics.snapshot()
        shapes = {int(s) for s in snap["shape_counts"]}
        assert shapes <= {1, 8, 64}
        assert snap["requests"] == len(reqs)
        # occupancy accounting is consistent with the shape counts
        padded = sum(
            int(s) * c for s, c in snap["shape_counts"].items()
        )
        assert snap["batch_occupancy_mean"] == pytest.approx(
            len(reqs) / padded
        )

    def test_max_wait_coalesces_trickled_requests(self, served):
        """With a linger window, requests trickling in one at a time
        still form a multi-row batch."""
        _, ds, lm, bank, programs = served
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)[:8]
        with MicroBatcher(
            lambda: bank, programs, metrics, max_wait_s=0.25
        ) as mb:
            futs = [mb.submit(r) for r in reqs]
            for f in futs:
                f.result()
        snap = metrics.snapshot()
        assert snap["dispatches"] < len(reqs)


class TestProgramCache:
    def _bank(self, rng, d):
        from photon_ml_tpu.serving import bank_from_arrays

        return bank_from_arrays(
            fixed=[(
                "global", "g",
                rng.standard_normal(d).astype(np.float32),
            )],
            shard_widths={"g": 4},
        )

    def test_eviction_is_lru_not_fifo(self, rng):
        """Eviction under spec churn drops the COLDEST entry: a rung
        the live bank just used survives insertions from another spec
        (FIFO would evict it and force a hot-path recompile)."""
        bank_a = self._bank(rng, 16)
        bank_b = self._bank(rng, 32)
        programs = ServingPrograms((1, 8), max_entries=3)
        programs.ensure_compiled(bank_a)
        # touch (spec_a, 1): now the most recently used entry
        assert programs.executable(bank_a.spec, 1) is not None
        programs.ensure_compiled(bank_b)  # 4th insert evicts ONE entry
        assert programs.executable(bank_a.spec, 1) is not None, (
            "LRU must keep the just-used rung"
        )
        assert programs.executable(bank_a.spec, 8) is None, (
            "the untouched rung is the eviction victim"
        )

    def test_concurrent_warmup_compiles_each_shape_once(self, rng):
        """ensure_compiled is single-flight per (spec, shape): racing
        threads never compile the same program twice."""
        bank = self._bank(rng, 16)
        programs = ServingPrograms((1, 8, 64))
        errors = []

        def warm():
            try:
                programs.ensure_compiled(bank)
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=warm) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert programs.stats()["compile_count"] == 3


class TestMicroBatchDemux:
    def test_concurrent_submitters_each_get_their_own_score(self, served):
        """The demux invariant under contention: N threads hammering
        submit() each receive exactly their request's row."""
        _, ds, lm, bank, programs = served
        ref = batch_reference_scores(lm, ds)
        reqs = requests_from_dataset(ds, bank)
        errors = []

        def worker(idx):
            try:
                for i in idx:
                    got = mb.score(reqs[i])
                    assert got == ref[i], (i, got, ref[i])
            except BaseException as e:
                errors.append(e)

        with MicroBatcher(lambda: bank, programs) as mb:
            threads = [
                threading.Thread(
                    target=worker, args=(range(t, len(reqs), 6),)
                )
                for t in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors, errors

    def test_submit_after_close_raises(self, served):
        _, ds, _, bank, programs = served
        reqs = requests_from_dataset(ds, bank)
        mb = MicroBatcher(lambda: bank, programs)
        mb.close()
        with pytest.raises(RuntimeError, match="closed"):
            mb.submit(reqs[0])


class TestCompileAndReadbackContract:
    def test_zero_recompiles_after_warmup(self, served):
        """After ensure_compiled walks the ladder, a replayed trace
        lowers NOTHING — every dispatch hits a precompiled executable
        (the AOT fixed-shape contract, pinned with jax's own counter)."""
        import jax._src.test_util as jtu

        _, ds, lm, bank, programs = served
        reqs = requests_from_dataset(ds, bank)
        before = programs.stats()
        with MicroBatcher(lambda: bank, programs) as mb:
            with jtu.count_jit_and_pmap_lowerings() as count:
                futs = [mb.submit(r) for r in reqs]
                for f in futs:
                    f.result()
        assert count() == 0, f"request path lowered {count()} program(s)"
        after = programs.stats()
        assert after["compile_count"] == before["compile_count"]
        assert after["cold_dispatch_compiles"] == 0

    def test_exactly_one_readback_per_dispatched_batch(self, served):
        _, ds, lm, bank, programs = served
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            overlap.reset_readback_stats()
            futs = [mb.submit(r) for r in reqs]
            for f in futs:
                f.result()
            assert overlap.readback_stats() == metrics.snapshot()[
                "dispatches"
            ]


class TestHotSwap:
    def _save(self, lm, ds, path, rng):
        """Persist a LoadedGameModel-shaped model through the real
        artifact writer (reference directory layout)."""
        from photon_ml_tpu.game.model_io import save_game_model
        from photon_ml_tpu.game.model import (
            FixedEffectModel,
            GameModel,
        )
        from photon_ml_tpu.models.coefficients import Coefficients
        from photon_ml_tpu.models.glm import create_model

        shard_id, means = lm.fixed_effects["global"]
        imap = ds.shards[shard_id].index_map
        w = np.zeros((imap.size,), np.float32)
        for k, v in means.items():
            i = imap.get_index(k)
            if i >= 0:
                w[i] = v
        gm = GameModel({
            "global": FixedEffectModel(
                create_model(
                    TaskType.LOGISTIC_REGRESSION,
                    Coefficients(jnp.asarray(w)),
                ),
                shard_id,
            )
        })
        save_game_model(gm, ds, path)

    def _fe_only(self, rng, scale):
        lm = LoadedGameModel()
        lm.fixed_effects["global"] = (
            "g", {f"g{j}\t": float(rng.normal()) * scale for j in range(5)},
        )
        return lm

    @pytest.fixture
    def two_generations(self, rng, tmp_path):
        recs = synth_records(rng)
        ds = build_game_dataset(recs, [SHARDS[0]], [])
        gens = {}
        for name, scale in (("g1", 1.0), ("g2", -2.0)):
            lm = self._fe_only(rng, scale)
            self._save(lm, ds, str(tmp_path / name), rng)
            gens[name] = lm
        return ds, gens, tmp_path

    def _serving_model(self, ds, model_dir):
        imaps = {"g": ds.shards["g"].index_map}
        widths = {"g": ds.shards["g"].indices.shape[1]}
        return ServingModel.load(
            str(model_dir), imaps, widths, ladder=(1, 8)
        ), imaps, widths

    def test_swap_parity_mid_load(self, two_generations):
        """Requests completing before the flip score generation 1,
        requests after score generation 2, and the swapped bank is
        BITWISE the bank a fresh load of generation 2 builds — through
        the donating refresh path (same shapes)."""
        ds, gens, tmp = two_generations
        sm, imaps, widths = self._serving_model(ds, tmp / "g1")
        ref1 = batch_reference_scores(gens["g1"], ds)
        ref2 = batch_reference_scores(gens["g2"], ds)
        reqs = requests_from_dataset(ds, sm.current())
        with MicroBatcher(sm.current, sm.programs) as mb:
            for i in range(5):
                assert mb.score(reqs[i]) == ref1[i]
            res = sm.stage_and_swap(str(tmp / "g2"))
            assert res.ok and res.generation == 2
            assert res.donated, "same-shape swap must take the donated path"
            assert res.recompiled_programs == 0
            for i in range(5, 10):
                assert mb.score(reqs[i]) == ref2[i]
        fresh = build_model_bank(gens["g2"], imaps, widths)
        assert np.array_equal(
            overlap.device_get(sm.current().arrays["global"]),
            overlap.device_get(fresh.arrays["global"]),
        ), "donated refresh must be a bitwise move"
        assert sm.current().generation == 2

    def test_swap_under_concurrent_traffic(self, two_generations):
        """Flip while submitters hammer: every result is EITHER gen-1's
        or gen-2's score for its row (a flip lands on a batch boundary,
        never inside one), and after the swap only gen-2 scores appear."""
        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        ref1 = batch_reference_scores(gens["g1"], ds)
        ref2 = batch_reference_scores(gens["g2"], ds)
        reqs = requests_from_dataset(ds, sm.current())
        errors = []

        def worker(idx):
            try:
                for i in idx:
                    got = mb.score(reqs[i])
                    assert got in (ref1[i], ref2[i]), (i, got)
            except BaseException as e:
                errors.append(e)

        with MicroBatcher(sm.current, sm.programs) as mb:
            threads = [
                threading.Thread(
                    target=worker, args=(range(t, len(reqs), 4),)
                )
                for t in range(4)
            ]
            for t in threads:
                t.start()
            sm.stage_and_swap(str(tmp / "g2"))
            for t in threads:
                t.join()
            assert not errors, errors
            for i in range(4):
                assert mb.score(reqs[i]) == ref2[i]

    def test_batcher_autowires_the_dispatch_lock(self, two_generations):
        """A bound ServingModel.current bank_ref hands the batcher the
        swap/dispatch exclusion lock automatically: a DONATING flip
        (which invalidates generation N's buffers) can never overlap a
        dispatch that is executing against them."""
        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        mb = MicroBatcher(sm.current, sm.programs)
        try:
            assert mb._swap_lock is sm.dispatch_lock
        finally:
            mb.close()
        plain = MicroBatcher(lambda: sm.current(), sm.programs)
        try:
            assert plain._swap_lock is None
        finally:
            plain.close()

    def test_repeated_swaps_under_fire_never_break_a_dispatch(
        self, two_generations
    ):
        """Donation stress: flip generations repeatedly while
        submitters hammer — no dispatch may ever observe a donated
        (deleted) buffer, and every result matches one generation."""
        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        ref1 = batch_reference_scores(gens["g1"], ds)
        ref2 = batch_reference_scores(gens["g2"], ds)
        reqs = requests_from_dataset(ds, sm.current())
        errors = []
        stop = threading.Event()

        def submitter():
            try:
                i = 0
                while not stop.is_set():
                    got = mb.score(reqs[i % len(reqs)])
                    j = i % len(reqs)
                    assert got in (ref1[j], ref2[j]), (j, got)
                    i += 1
            except BaseException as e:
                errors.append(e)

        with MicroBatcher(sm.current, sm.programs) as mb:
            threads = [
                threading.Thread(target=submitter) for _ in range(3)
            ]
            for t in threads:
                t.start()
            try:
                for gen_dir in ("g2", "g1", "g2", "g1", "g2"):
                    res = sm.stage_and_swap(str(tmp / gen_dir))
                    assert res.ok and res.donated, res
            finally:
                stop.set()
                for t in threads:
                    t.join()
        assert not errors, errors
        assert sm.current().generation == 6

    def test_corrupt_swap_quarantines_and_rolls_back(
        self, two_generations
    ):
        """An injected CORRUPT at the serving.model_load seam during
        staging: the artifact moves to *.corrupt, the swap reports
        rolled_back, and generation 1 keeps serving bit-identically."""
        from photon_ml_tpu.reliability import install_plan
        from photon_ml_tpu.reliability.retry import (
            reset_retry_stats,
            retry_stats,
        )

        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        ref1 = batch_reference_scores(gens["g1"], ds)
        reqs = requests_from_dataset(ds, sm.current())
        victim = str(tmp / "g2-copy")
        shutil.copytree(str(tmp / "g2"), victim)
        reset_retry_stats()
        install_plan("serving.model_load:1:CORRUPT")
        try:
            res = sm.stage_and_swap(victim)
        finally:
            install_plan(None)
        assert not res.ok and res.rolled_back
        assert res.quarantined and os.path.exists(res.quarantined)
        assert not os.path.exists(victim)
        assert (
            retry_stats()["quarantined"].get("serving.model_load", 0) == 1
        )
        assert sm.current().generation == 1
        with MicroBatcher(sm.current, sm.programs) as mb:
            for i in range(3):
                assert mb.score(reqs[i]) == ref1[i]

    def test_transient_load_fault_retries(self, two_generations):
        """A once-EIO at the seam is absorbed by the retry budget: the
        swap still completes and the retry is accounted."""
        from photon_ml_tpu.reliability import install_plan
        from photon_ml_tpu.reliability.retry import (
            reset_retry_stats,
            retry_stats,
        )

        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        reset_retry_stats()
        install_plan("serving.model_load:1:EIO")
        try:
            res = sm.stage_and_swap(str(tmp / "g2"))
        finally:
            install_plan(None)
        assert res.ok and res.generation == 2
        assert retry_stats()["retries"].get("serving.model_load", 0) >= 1

    def test_entity_set_change_resolves_rows_at_dispatch(self, rng):
        """The case entity padding exists for: generation 2 adds an
        entity inside the same padded bucket, and the new id sorts
        BEFORE existing ones so every bank row shifts. The swap is
        donated (same spec), yet requests built BEFORE the swap — both
        the dataset-replay path and the stdin path — must score
        generation 2 bitwise: entity ids resolve to bank rows at
        dispatch time, never at request-build time."""
        recs = synth_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        lm1 = synth_model(rng)
        lm2 = synth_model(rng, scale=-1.5)
        # "user00" sorts between "user0" and "user1": rows of
        # user1..user5 all shift by one in generation 2's bank
        lm2.random_effects["per-user"][2]["user00"] = {
            "u0\t": 3.0, "u1\t": -2.0, "u2\t": 1.0
        }
        bank1 = make_bank(lm1, ds)
        sm = ServingModel(bank1, ServingPrograms((1, 8, 64)))
        ref1 = batch_reference_scores(lm1, ds)
        ref2 = batch_reference_scores(lm2, ds)
        reqs = requests_from_dataset(ds, bank1)  # pre-built, gen 1
        stdin_reqs = [
            request_from_record(recs[i], bank1, SHARDS) for i in (1, 9)
        ]
        imaps = {sid: sd.index_map for sid, sd in ds.shards.items()}
        widths = {sid: sd.indices.shape[1] for sid, sd in ds.shards.items()}
        staged = build_model_bank(lm2, imaps, widths, device=False)
        with MicroBatcher(sm.current, sm.programs) as mb:
            for i in range(3):
                assert mb.score(reqs[i]) == ref1[i]
            res = sm.swap_to_bank(staged)
            assert res.ok and res.generation == 2
            assert res.donated, "same padded bucket must stay donated"
            assert res.recompiled_programs == 0
            got = np.asarray(
                [mb.score(r) for r in reqs], np.float32
            )
            assert np.array_equal(got, ref2), (
                "pre-swap requests scored stale bank rows"
            )
            for req, i in zip(stdin_reqs, (1, 9)):
                assert mb.score(req) == ref2[i]

    def test_second_donated_swap_lowers_nothing(self, rng):
        """After the first donating swap compiles the refresh program
        (during staging, OFF the request path), further same-shape swaps
        are all-cache-hit: zero lowerings, including the refresh."""
        import jax._src.test_util as jtu

        recs = synth_records(rng)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        imaps = {sid: sd.index_map for sid, sd in ds.shards.items()}
        widths = {sid: sd.indices.shape[1] for sid, sd in ds.shards.items()}
        sm = ServingModel(
            make_bank(synth_model(rng), ds), ServingPrograms((1, 8))
        )
        sm.swap_to_bank(
            build_model_bank(synth_model(rng, scale=2.0), imaps, widths,
                             device=False)
        )
        staged = build_model_bank(
            synth_model(rng, scale=-3.0), imaps, widths, device=False
        )
        with jtu.count_jit_and_pmap_lowerings() as count:
            res = sm.swap_to_bank(staged)
        assert res.ok and res.donated
        assert count() == 0, (
            f"donated swap lowered {count()} program(s) after warmup"
        )

    def test_exhausted_load_budget_rolls_back(self, two_generations):
        from photon_ml_tpu.reliability import install_plan

        ds, gens, tmp = two_generations
        sm, _, _ = self._serving_model(ds, tmp / "g1")
        install_plan("serving.model_load:1:EIO:*")
        try:
            res = sm.stage_and_swap(str(tmp / "g2"))
        finally:
            install_plan(None)
        assert not res.ok and res.rolled_back
        assert sm.current().generation == 1
        # a transient give-up does NOT quarantine the (healthy) artifact
        assert os.path.isdir(str(tmp / "g2"))


class TestVectorizedScoreRecords:
    """Satellite: the batch scorer's record assembly is a vectorized,
    sliceable, re-iterable column view — same records as the old
    per-row loop, no per-cell Python casts, retry-safe."""

    def _rows(self, rng):
        from photon_ml_tpu.cli.game_scoring_driver import (
            GameScoringDriver,
            GameScoringParams,
        )

        recs = synth_records(rng, n=20)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        scores = np.asarray(rng.normal(size=ds.num_real_rows), np.float32)
        params = GameScoringParams.__new__(GameScoringParams)
        params.has_response = True
        params.model_id = "m7"
        fake = GameScoringDriver.__new__(GameScoringDriver)
        fake.params = params
        return ds, scores, GameScoringDriver._score_records(
            fake, ds, scores
        )

    def _expected(self, ds, scores):
        id_types = sorted(ds.entity_indexes)
        out = []
        for i in range(ds.num_real_rows):
            meta = {
                t: ds.entity_indexes[t].ids[int(ds.entity_codes[t][i])]
                for t in id_types
                if int(ds.entity_codes[t][i]) >= 0
            }
            out.append({
                "uid": ds.uids[i],
                "label": float(ds.labels[i]),
                "modelId": "m7",
                "predictionScore": float(scores[i]),
                "weight": float(ds.weights[i]),
                "metadataMap": meta or None,
            })
        return out

    def test_rows_match_reference_loop(self, rng):
        ds, scores, rows = self._rows(rng)
        assert len(rows) == ds.num_real_rows
        assert list(rows) == self._expected(ds, scores)

    def test_reiteration_and_split_slicing(self, rng):
        ds, scores, rows = self._rows(rng)
        first = list(rows)
        assert list(rows) == first, "view must re-iterate identically"
        expected = self._expected(ds, scores)
        n = 3
        split = [list(rows[i::n]) for i in range(n)]
        assert [r for part in split for r in part] != []
        for i in range(n):
            assert split[i] == expected[i::n]


def _wait_until(cond, timeout=5.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


class TestAdmissionControl:
    """ISSUE 8: deadlines, load shedding and bounded submit — every
    request reaches exactly one NAMED terminal outcome, fast."""

    def _blocked_batcher(self, served, **kw):
        """A batcher whose dispatcher is parked on a held lock — the
        deterministic way to build queue depth."""
        _, ds, lm, bank, programs = served
        gate = threading.Lock()
        gate.acquire()
        metrics = ServingMetrics()
        mb = MicroBatcher(
            lambda: bank, programs, metrics, swap_lock=gate, **kw
        )
        reqs = requests_from_dataset(ds, bank)
        return mb, metrics, reqs, gate

    def test_predicted_wait_sheds_immediately(self, served):
        """Admission refuses a deadlined request UP FRONT when the EWMA
        service model says the queue already costs more than its
        deadline — no queue slot, no device work, a named SHED."""
        admission = AdmissionController()
        admission.note_dispatch(rows=1, busy_s=10.0)  # 10s per row
        _, ds, lm, bank, programs = served
        gate = threading.Lock()
        gate.acquire()
        metrics = ServingMetrics()
        mb = MicroBatcher(
            lambda: bank, programs, metrics,
            swap_lock=gate, admission=admission,
        )
        reqs = requests_from_dataset(ds, bank)
        try:
            f1 = mb.submit(reqs[0])  # claimed by the blocked dispatcher
            assert _wait_until(lambda: not mb._queue and mb._inflight)
            f2 = mb.submit(reqs[1])  # no deadline: admitted, queued
            r3 = reqs[2]
            r3.deadline_ms = 50.0
            t0 = time.perf_counter()
            with pytest.raises(RequestShed, match="predicted queue wait"):
                mb.submit(r3)
            assert time.perf_counter() - t0 < 1.0, "shed must be instant"
        finally:
            gate.release()
        assert isinstance(f1.result(timeout=30), float)
        assert isinstance(f2.result(timeout=30), float)
        mb.close()
        assert metrics.snapshot()["sheds"] == {
            "predicted_wait": 1, "total": 1,
        }

    def test_full_queue_submit_sheds_after_bounded_wait(self, served):
        """The round-12 indefinite block is gone: a submitter facing a
        full queue waits at most its own deadline, then gets SHED."""
        mb, metrics, reqs, gate = self._blocked_batcher(
            served, max_queue=1
        )
        try:
            f1 = mb.submit(reqs[0])
            assert _wait_until(lambda: not mb._queue and mb._inflight)
            f2 = mb.submit(reqs[1])  # fills the queue
            r3 = reqs[2]
            r3.deadline_ms = 100.0
            t0 = time.perf_counter()
            with pytest.raises(RequestShed, match="queue full"):
                mb.submit(r3)
            elapsed = time.perf_counter() - t0
            assert 0.05 < elapsed < 5.0, elapsed
        finally:
            gate.release()
        assert isinstance(f1.result(timeout=30), float)
        assert isinstance(f2.result(timeout=30), float)
        mb.close()
        assert metrics.snapshot()["sheds"]["queue_full"] == 1

    def test_expired_request_dropped_before_dispatch(self, served):
        """A deadline that passes in the queue fails the future with
        DeadlineExceeded and the device NEVER scores the dead row (the
        dispatch count does not move)."""
        mb, metrics, reqs, gate = self._blocked_batcher(served)
        try:
            f1 = mb.submit(reqs[0])
            assert _wait_until(lambda: not mb._queue and mb._inflight)
            r2 = reqs[1]
            r2.deadline_ms = 20.0
            f2 = mb.submit(r2)
            time.sleep(0.1)  # let the deadline lapse while queued
        finally:
            gate.release()
        assert isinstance(f1.result(timeout=30), float)
        with pytest.raises(DeadlineExceeded, match="deadline"):
            f2.result(timeout=30)
        mb.close()
        snap = metrics.snapshot()
        assert snap["deadline_expired"] == 1
        assert snap["dispatches"] == 1, (
            "the expired request must never reach the device"
        )

    def test_default_deadline_applies_to_undeadlined_requests(
        self, served
    ):
        _, ds, lm, bank, programs = served
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(
            lambda: bank, programs, default_deadline_ms=1234.0
        ) as mb:
            assert reqs[0].deadline_ms is None
            mb.score(reqs[0])
            assert reqs[0].deadline_ms == 1234.0

    def test_outcome_is_an_annotated_float(self, served):
        _, ds, lm, bank, programs = served
        ref = batch_reference_scores(lm, ds)
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(lambda: bank, programs) as mb:
            out = mb.score(reqs[0])
        assert isinstance(out, ScoreOutcome)
        assert out == ref[0]  # still a float, still bitwise
        assert out.degraded is False
        assert out.generation == bank.generation

    def test_record_deadline_propagates(self, served):
        recs, ds, lm, bank, _ = served
        rec = dict(recs[0])
        rec["deadline_ms"] = 75.5
        req = request_from_record(rec, bank, SHARDS)
        assert req.deadline_ms == 75.5
        assert request_from_record(recs[0], bank, SHARDS).deadline_ms is None


class TestGracefulDegradation:
    """ISSUE 8: RE-bank trouble degrades to the FE-only score (bitwise
    the batch scorer's unknown-entity semantics) with a flag — never a
    failed request."""

    def _fe_only_reference(self, lm, ds):
        fe = LoadedGameModel()
        fe.fixed_effects = dict(lm.fixed_effects)
        return batch_reference_scores(fe, ds)

    def test_quarantined_re_scores_fe_only_bitwise(self, served):
        _, ds, lm, bank, programs = served
        ref_fe = self._fe_only_reference(lm, ds)
        ref_full = batch_reference_scores(lm, ds)
        assert not np.array_equal(ref_fe, ref_full), (
            "fixture must make degradation observable"
        )
        bank.quarantine_re("userId")
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            outs = [mb.score(r) for r in reqs]
        got = np.asarray(outs, np.float32)
        assert np.array_equal(got, ref_fe), (
            "degraded scores must be bitwise the batch scorer's "
            "FE-only path"
        )
        assert all(o.degraded for o in outs)
        assert metrics.snapshot()["degraded_responses"] == len(reqs)

    def test_unknown_re_type_quarantine_rejected(self, served):
        _, ds, lm, bank, _ = served
        with pytest.raises(ValueError, match="unknown random-effect"):
            bank.quarantine_re("no-such-type")

    def test_row_resolution_failure_degrades_then_quarantines(
        self, served
    ):
        """A dying entity index (e.g. the native mmap store lost mid-
        swap) degrades affected rows FE-only; after RE_QUARANTINE_AFTER
        consecutive failures the type is quarantined so later requests
        stop paying the failing lookup."""
        from photon_ml_tpu.serving.batcher import RE_QUARANTINE_AFTER

        _, ds, lm, bank, programs = served
        ref_fe = self._fe_only_reference(lm, ds)

        class DyingIndex:
            calls = 0

            def rows_of(self, ids):
                DyingIndex.calls += 1
                raise RuntimeError("entity store died")

        bank.entity_rows["userId"] = DyingIndex()
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        n = RE_QUARANTINE_AFTER + 2
        with MicroBatcher(lambda: bank, programs, metrics) as mb:
            outs = [mb.score(reqs[i]) for i in range(n)]
        got = np.asarray(outs, np.float32)
        assert np.array_equal(got, ref_fe[:n])
        assert all(o.degraded for o in outs)
        assert "userId" in bank.quarantined_re_types
        # after quarantine the failing store is no longer consulted
        assert DyingIndex.calls == RE_QUARANTINE_AFTER
        snap = metrics.snapshot()
        assert snap["re_resolution_failures"] == {
            "userId": RE_QUARANTINE_AFTER
        }
        assert snap["re_quarantines"] == {"userId": 1}
        assert snap["degraded_responses"] == n

    def test_swap_installs_a_clean_bank(self, served, rng):
        """Quarantine is per-generation: a hot swap's fresh bank starts
        with no quarantined coordinates."""
        _, ds, lm, bank, programs = served
        bank.quarantine_re("userId")
        sm = ServingModel(bank, programs)
        imaps = {sid: sd.index_map for sid, sd in ds.shards.items()}
        widths = {sid: sd.indices.shape[1] for sid, sd in ds.shards.items()}
        staged = build_model_bank(
            synth_model(rng, scale=2.0), imaps, widths, device=False
        )
        res = sm.swap_to_bank(staged)
        assert res.ok
        assert sm.current().quarantined_re_types == set()


class TestShutdownAndDrain:
    """Satellites 1+3: close/drain semantics — blocked submitters wake
    and raise, every in-flight future reaches exactly one terminal
    state, and a bounded drain never leaves a hung future."""

    def test_close_under_saturated_queue_wakes_blocked_submitters(
        self, served
    ):
        """Satellite 1: a submitter parked on a FULL queue must wake
        and raise when another thread closes the batcher — not hang."""
        _, ds, lm, bank, programs = served
        gate = threading.Lock()
        gate.acquire()
        mb = MicroBatcher(
            lambda: bank, programs, swap_lock=gate, max_queue=1
        )
        reqs = requests_from_dataset(ds, bank)
        f1 = mb.submit(reqs[0])
        assert _wait_until(lambda: not mb._queue and mb._inflight)
        f2 = mb.submit(reqs[1])  # saturates the queue
        blocked_outcome = []

        def blocked_submitter():
            try:
                mb.submit(reqs[2])
                blocked_outcome.append("admitted")
            except BatcherClosed:
                blocked_outcome.append("closed")
            except BaseException as e:  # pragma: no cover
                blocked_outcome.append(e)

        t = threading.Thread(target=blocked_submitter)
        t.start()
        time.sleep(0.1)  # park it on the full queue
        closer = threading.Thread(target=mb.close)
        closer.start()
        t.join(timeout=10)
        assert not t.is_alive(), "blocked submitter hung across close()"
        assert blocked_outcome == ["closed"]
        gate.release()  # let the dispatcher finish the claimed work
        closer.join(timeout=10)
        assert not closer.is_alive()
        # the admitted requests still reached their terminal results
        assert isinstance(f1.result(timeout=10), float)
        assert isinstance(f2.result(timeout=10), float)

    def test_clean_close_resolves_every_future(self, served):
        _, ds, lm, bank, programs = served
        reqs = requests_from_dataset(ds, bank)
        mb = MicroBatcher(lambda: bank, programs)
        futs = [mb.submit(r) for r in reqs]
        mb.close()
        assert all(f.done() for f in futs)
        assert [f.result(timeout=0) for f in futs]

    def test_drain_serves_queue_inside_budget(self, served):
        _, ds, lm, bank, programs = served
        metrics = ServingMetrics()
        reqs = requests_from_dataset(ds, bank)
        mb = MicroBatcher(lambda: bank, programs, metrics)
        futs = [mb.submit(r) for r in reqs]
        report = mb.drain(30.0)
        assert report.failed == 0 and not report.timed_out
        assert all(f.done() for f in futs)
        assert [f.result(timeout=0) for f in futs]
        assert metrics.snapshot()["drain"]["failed"] == 0
        with pytest.raises(BatcherClosed):
            mb.submit(reqs[0])

    def test_drain_timeout_fails_leftovers_with_named_error(
        self, served
    ):
        """A wedged dispatcher cannot turn SIGTERM into a hang: at the
        budget, every still-pending future (queued AND in-flight) fails
        with DRAIN_TIMEOUT — exactly one terminal outcome each."""
        _, ds, lm, bank, programs = served
        gate = threading.Lock()
        gate.acquire()
        metrics = ServingMetrics()
        mb = MicroBatcher(lambda: bank, programs, metrics, swap_lock=gate)
        reqs = requests_from_dataset(ds, bank)
        futs = [mb.submit(r) for r in reqs[:5]]
        assert _wait_until(lambda: mb._inflight)
        report = mb.drain(0.3)
        assert report.timed_out and report.failed == len(futs)
        for f in futs:
            assert f.done(), "drain left a hung future"
            with pytest.raises(DrainTimeout):
                f.result(timeout=0)
        snap = metrics.snapshot()
        assert snap["drain"]["failed"] == len(futs)
        assert snap["drain"]["timed_out"] is True
        # un-wedge: the dispatcher finishes its claimed batch, finds
        # every future already terminal (no double resolution), exits
        gate.release()
        assert _wait_until(lambda: not mb.alive(), timeout=10)

    def test_drain_is_idempotent_after_close(self, served):
        _, ds, lm, bank, programs = served
        mb = MicroBatcher(lambda: bank, programs)
        mb.close()
        report = mb.drain(1.0)
        assert report.pending_at_start == 0 and report.failed == 0

    def test_heartbeat_beats_while_idle(self, served):
        _, ds, lm, bank, programs = served
        with MicroBatcher(lambda: bank, programs) as mb:
            assert mb.alive()
            time.sleep(0.6)  # > 2 heartbeat intervals, zero traffic
            assert mb.heartbeat_age_s() < 0.5, (
                "idle dispatcher must keep beating"
            )

    def test_kill_fault_plan_dies_instead_of_hanging(self, tmp_path):
        """Satellite 3, the KILL arm: a deterministic SIGKILL at the
        serving.dispatch crossing kills the process AT that crossing —
        promptly (no drain, no atexit, no hang), which is the crash the
        resume/ops machinery must assume."""
        script = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from photon_ml_tpu.reliability import install_plan
from photon_ml_tpu.serving import (
    MicroBatcher, ScoreRequest, ServingPrograms, bank_from_arrays,
)

bank = bank_from_arrays(
    fixed=[("global", "g", np.ones(8, np.float32))],
    shard_widths={"g": 2},
)
programs = ServingPrograms((1, 4))
programs.ensure_compiled(bank)
install_plan("serving.dispatch:1:KILL")
mb = MicroBatcher(lambda: bank, programs)
fut = mb.submit(ScoreRequest(
    uid="x",
    indices={"g": np.zeros(2, np.int32)},
    values={"g": np.zeros(2, np.float32)},
    entity_ids={},
))
import time
time.sleep(60)
print("SURVIVED")
"""
        r = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == -9, (r.returncode, r.stdout, r.stderr)
        assert "SURVIVED" not in r.stdout


class TestServingDriverValidation:
    def _params(self, **kw):
        from photon_ml_tpu.cli.serving_driver import ServingParams

        base = dict(
            game_model_input_dir="m",
            output_dir="o",
            request_paths=["trace"],
            feature_shards=[SHARDS[0]],
        )
        base.update(kw)
        return ServingParams(**base)

    def test_stdin_requires_prebuilt_maps_and_width(self):
        with pytest.raises(ValueError, match="prebuilt feature maps"):
            self._params(request_paths=["-"]).validate()
        with pytest.raises(ValueError, match="request-nnz-width"):
            self._params(
                request_paths=["-"], offheap_indexmap_dir="idx"
            ).validate()

    def test_swap_requires_threshold(self):
        with pytest.raises(ValueError, match="swap-after-requests"):
            self._params(swap_model_dir="m2").validate()

    def test_bad_ladder_and_mode(self):
        with pytest.raises(ValueError, match="ladder"):
            self._params(ladder=[8, 1]).validate()
        with pytest.raises(ValueError, match="mode"):
            self._params(mode="burst").validate()


@pytest.mark.slow
class TestServingDriverEndToEnd:
    def _train(self, tmp_path, rng):
        from tests.test_game_drivers import write_game_avro
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            GameTrainingParams,
        )
        from photon_ml_tpu.game.config import (
            FixedEffectDataConfiguration,
            RandomEffectDataConfiguration,
        )

        train = tmp_path / "train"
        train.mkdir()
        write_game_avro(str(train / "p0.avro"), rng)
        params = GameTrainingParams(
            train_input_dirs=[str(train)],
            output_dir=str(tmp_path / "out"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=[
                FeatureShardConfiguration("g", ["features"]),
                FeatureShardConfiguration("u", ["userFeatures"]),
            ],
            fixed_effect_data_configs={
                "global": FixedEffectDataConfiguration("g")
            },
            fixed_effect_opt_configs={"global": "10,1e-6,0.1,1,LBFGS,L2"},
            random_effect_data_configs={
                "per-user": RandomEffectDataConfiguration("userId", "u")
            },
            random_effect_opt_configs={"per-user": "10,1e-6,1.0,1,LBFGS,L2"},
            num_iterations=1,
        )
        GameTrainingDriver(params).run()
        return str(train), os.path.join(params.output_dir, "best-model")

    def test_replayed_trace_matches_batch_driver_bitwise(
        self, tmp_path, rng
    ):
        """Driver-level acceptance: the serving driver's score records
        equal the batch scoring driver's record for record, and its
        metrics.json carries the latency/occupancy/compile accounting."""
        from photon_ml_tpu.cli.game_scoring_driver import (
            GameScoringDriver,
            GameScoringParams,
        )
        from photon_ml_tpu.cli.serving_driver import (
            ServingDriver,
            params_from_args,
        )
        from photon_ml_tpu.io.avro_codec import read_avro_records

        train, model_dir = self._train(tmp_path, rng)
        shards = [
            FeatureShardConfiguration("g", ["features"]),
            FeatureShardConfiguration("u", ["userFeatures"]),
        ]
        GameScoringDriver(GameScoringParams(
            input_dirs=[train],
            game_model_input_dir=model_dir,
            output_dir=str(tmp_path / "batch"),
            task_type=TaskType.LOGISTIC_REGRESSION,
            feature_shards=shards,
        )).run()
        driver = ServingDriver(params_from_args([
            "--game-model-input-dir", model_dir,
            "--output-dir", str(tmp_path / "serve"),
            "--request-paths", train,
            "--feature-shard-id-to-feature-section-keys-map",
            "g:features|u:userFeatures",
            "--mode", "open",
            "--concurrency", "4",
            "--evaluator-types", "AUC",
        ]))
        driver.run()
        batch = {
            r["uid"]: r
            for r in read_avro_records(str(tmp_path / "batch" / "scores"))
        }
        serve = {
            r["uid"]: r
            for r in read_avro_records(str(tmp_path / "serve" / "scores"))
        }
        assert batch == serve
        m = json.load(open(str(tmp_path / "serve" / "metrics.json")))
        assert m["programs"]["cold_dispatch_compiles"] == 0
        assert m["readbacks"] == m["serving"]["dispatches"]
        assert m["serving"]["latency_p99_ms"] > 0
        assert m["serving"]["qps"] > 0
        assert 0 < m["AUC"] <= 1

    def test_driver_hot_swap_mid_replay(self, tmp_path, rng):
        """--swap-model-dir flips generations mid-trace: both
        generations appear in the dispatch accounting and the swap
        history records a donated, non-recompiling flip."""
        from photon_ml_tpu.cli.serving_driver import (
            ServingDriver,
            params_from_args,
        )

        train, model_dir = self._train(tmp_path, rng)
        driver = ServingDriver(params_from_args([
            "--game-model-input-dir", model_dir,
            "--output-dir", str(tmp_path / "serve-swap"),
            "--request-paths", train,
            "--feature-shard-id-to-feature-section-keys-map",
            "g:features|u:userFeatures",
            "--swap-model-dir", model_dir,
            "--swap-after-requests", "40",
        ]))
        driver.run()
        m = json.load(
            open(str(tmp_path / "serve-swap" / "metrics.json"))
        )
        assert m["generation"] == 2
        swaps = m["swap_history"]
        assert len(swaps) == 1 and swaps[0]["ok"] and swaps[0]["donated"]
        assert swaps[0]["recompiled_programs"] == 0
        gens = m["serving"]["generation_dispatches"]
        assert set(gens) == {"1", "2"}
