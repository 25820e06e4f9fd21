"""Tiled sparse kernel tests (interpret mode on CPU): schedule invariants
and exact agreement with the scatter/gather GLMObjective on random
problems, including duplicates, skewed (intercept-like) features and
multi-window shapes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.ops.losses import LOGISTIC, LINEAR
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.tiled_sparse import (
    TileParams,
    TiledGLMObjective,
    tiled_batch_from_sparse,
)

PARAMS = TileParams(s_hi=8, s_lo=8, chunk=32)  # window 64, tiny for tests


def random_problem(rng, n=100, d=150, k=6, intercept=True):
    rows, labels = [], []
    for i in range(n):
        nnz = rng.integers(1, k + 1)
        ix = rng.choice(d - 1, size=nnz, replace=False).tolist()
        vs = rng.normal(size=nnz).tolist()
        if intercept:
            ix.append(d - 1)  # intercept-like skewed feature in EVERY row
            vs.append(1.0)
        labels.append(float(rng.uniform() > 0.5))
        rows.append((ix, vs))
    return make_sparse_batch(rows, labels, weights=rng.uniform(0.5, 2.0, n)), d


class TestSparseCoo:
    """SparseBatch -> COO triples, the schedule builders' input."""

    @pytest.mark.parametrize(
        "case", ["nothing_dropped", "explicit_zeros", "padding_rows", "both"]
    )
    def test_matches_the_entry_by_entry_filter(self, rng, case):
        from photon_ml_tpu.data.batch import SparseBatch
        from photon_ml_tpu.ops.tiled_sparse import _sparse_coo

        n, k, d = 40, 5, 90
        indices = rng.integers(0, d, (n, k)).astype(np.int32)
        values = rng.normal(size=(n, k)).astype(np.float32)
        weights = rng.uniform(0.5, 2.0, n).astype(np.float32)
        if case in ("explicit_zeros", "both"):
            values[rng.uniform(size=(n, k)) < 0.3] = 0.0
        if case in ("padding_rows", "both"):
            weights[n - 7:] = 0.0
        batch = SparseBatch(
            indices=jnp.asarray(indices), values=jnp.asarray(values),
            labels=jnp.zeros(n), offsets=jnp.zeros(n),
            weights=jnp.asarray(weights),
        )
        want = [
            (i, int(indices[i, j]), values[i, j])
            for i in range(n) for j in range(k)
            if values[i, j] != 0 and weights[i] > 0
        ]
        rows, feats, vals, rows_total = _sparse_coo(batch)
        assert rows_total == n and rows.dtype == feats.dtype == np.int64
        assert vals.dtype == np.float32
        assert list(zip(rows.tolist(), feats.tolist(), vals)) == want


class TestSchedule:
    def test_entries_preserved(self, rng):
        batch, d = random_problem(rng)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        # every nonzero entry appears exactly once in each schedule
        # (chunk slots + spill tail together)
        nnz = int(np.count_nonzero(np.asarray(batch.values)))
        for sched in (tb.z_sched, tb.g_sched):
            assert (
                np.count_nonzero(sched.vals)
                + np.count_nonzero(sched.spill_vals)
            ) == nnz
        # monotone output blocks
        z_out = np.asarray(tb.z_sched.step_out)
        g_out = np.asarray(tb.g_sched.step_out)
        assert np.all(np.diff(z_out) >= 0)
        assert np.all(np.diff(g_out) >= 0)
        # init flags exactly at block changes
        changes = np.nonzero(np.diff(z_out) > 0)[0] + 1
        inits = np.nonzero(np.asarray(tb.z_sched.step_init))[0]
        assert inits[0] == 0 and set(inits[1:].tolist()) == set(changes.tolist())

    def test_window_bounds(self, rng):
        batch, d = random_problem(rng)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        for sched in (tb.z_sched, tb.g_sched):
            assert int(sched.out_pos.max()) < PARAMS.window
            assert int(sched.in_pos.max()) < PARAMS.window
            assert int(sched.out_pos.min()) >= 0
            assert int(sched.in_pos.min()) >= 0


class TestAgainstReferenceObjective:
    def _pair(self, rng, **kw):
        batch, d = random_problem(rng, **kw)
        obj = GLMObjective(LOGISTIC, d)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        return batch, obj, tobj, tb, d

    def test_value_and_gradient(self, rng):
        batch, obj, tobj, tb, d = self._pair(rng)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.3)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.3)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-4)

    def test_offsets_respected(self, rng):
        batch, d = random_problem(rng)
        batch = batch._replace(
            offsets=jnp.asarray(rng.normal(size=batch.offsets.shape).astype(np.float32))
        )
        obj = GLMObjective(LOGISTIC, d)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.0)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.0)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-4)

    def test_hessian_vector(self, rng):
        batch, obj, tobj, tb, d = self._pair(rng)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        u = jnp.asarray(rng.normal(size=d).astype(np.float32))
        hv0 = obj.hessian_vector(w, u, batch, 0.2)
        hv1 = tobj.hessian_vector(w, u, tb, 0.2)
        np.testing.assert_allclose(np.asarray(hv1), np.asarray(hv0), atol=2e-4)

    def test_hessian_diagonal(self, rng):
        batch, obj, tobj, tb, d = self._pair(rng)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        h0 = obj.hessian_diagonal(w, batch, 0.1)
        h1 = tobj.hessian_diagonal(w, tb, 0.1)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=2e-4)

    def test_linear_loss_and_duplicates(self, rng):
        # duplicate (row, feature) entries must sum, matching the ELL path
        batch = make_sparse_batch(
            [([0, 0, 2], [1.0, 2.0, -1.0]), ([1, 2], [0.5, 0.5])],
            [1.0, 0.0],
        )
        d = 3
        obj = GLMObjective(LINEAR, d)
        tb = tiled_batch_from_sparse(batch, d, params=TileParams(4, 4, 8))
        tobj = TiledGLMObjective(LINEAR, d, interpret=True, mxu="highest")
        w = jnp.asarray([0.3, -0.2, 0.9], jnp.float32)
        v0, g0 = obj.value_and_gradient(w, batch, 0.0)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.0)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-5)

    def test_multi_window_dims(self, rng):
        # dimensions spanning several windows on both axes
        batch, d = random_problem(rng, n=200, d=500, k=10)
        obj = GLMObjective(LOGISTIC, d)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        assert tb.num_feat_blocks >= 8 and tb.num_row_blocks >= 4
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.05)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.05)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=3e-4)


class TestNormalizationParity:
    def test_normalized_matches_scatter_objective(self, rng):
        from photon_ml_tpu.ops.normalization import NormalizationContext

        batch, d = random_problem(rng)
        ctx = NormalizationContext(
            factor=jnp.asarray(rng.uniform(0.5, 2.0, d).astype(np.float32)),
            shift=jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1),
        )
        obj = GLMObjective(LOGISTIC, d, ctx)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, norm=ctx, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        u = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.2)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.2)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=3e-4)
        hv0 = obj.hessian_vector(w, u, batch, 0.2)
        hv1 = tobj.hessian_vector(w, u, tb, 0.2)
        np.testing.assert_allclose(np.asarray(hv1), np.asarray(hv0), atol=3e-4)
        hd0 = obj.hessian_diagonal(w, batch, 0.1)
        hd1 = tobj.hessian_diagonal(w, tb, 0.1)
        np.testing.assert_allclose(np.asarray(hd1), np.asarray(hd0), atol=3e-4)


class TestJitArgument:
    def test_batch_passes_through_jit(self, rng):
        """The batch must be a pytree jit ARGUMENT (not a baked constant):
        at ads scale the schedule is hundreds of MB and constant-folding it
        into the executable breaks compilation."""
        import jax

        batch, d = random_problem(rng)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        obj = GLMObjective(LOGISTIC, d)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))

        fn = jax.jit(tobj.value_and_gradient)
        v1, g1 = fn(w, tb, 0.1)
        v0, g0 = obj.value_and_gradient(w, batch, 0.1)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-4)


class TestBf16x2Precision:
    def test_fast_path_within_tolerance(self, rng):
        """Default bf16x2 MXU mode: ~1e-5 relative error vs exact math."""
        batch, d = random_problem(rng)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        fast = TiledGLMObjective(LOGISTIC, d, interpret=True)
        obj = GLMObjective(LOGISTIC, d)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.1)
        v1, g1 = fast.value_and_gradient(w, tb, 0.1)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-4)
        scale = float(np.max(np.abs(np.asarray(g0)))) + 1e-9
        np.testing.assert_allclose(
            np.asarray(g1) / scale, np.asarray(g0) / scale, atol=1e-4
        )


class TestMxuPackedOneHot:
    def test_mxu_onehot_bit_identical_to_compare(self, rng):
        """The MXU-packed positional expansion (squared-distance matmul +
        relu, the round-3 'pack the one-hot build onto the MXU' lever)
        must produce EXACT 0/1 one-hots — every mxu variant's output is
        bit-identical to the iota-compare build."""
        batch, d = random_problem(rng)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        for mxu in ("highest", "bf16x2w"):
            a = TiledGLMObjective(
                LOGISTIC, d, interpret=True, mxu=mxu, onehot="compare"
            )
            b = TiledGLMObjective(
                LOGISTIC, d, interpret=True, mxu=mxu, onehot="mxu"
            )
            va, ga = a.value_and_gradient(w, tb, 0.1)
            vb, gb = b.value_and_gradient(w, tb, 0.1)
            assert float(va) == float(vb), mxu
            np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))

    def test_unknown_onehot_rejected(self):
        with pytest.raises(ValueError, match="onehot"):
            TiledGLMObjective(LOGISTIC, 8, onehot="typo")


class TestEmptyWindows:
    def test_empty_feature_window_zero_grad(self, rng):
        """A feature window with NO entries must yield exactly-zero gradient
        (on TPU the output buffer is uninitialized unless the schedule
        names every block — regression test for the missing-init bug)."""
        win = PARAMS.window
        d = 3 * win  # three feature windows; the middle one stays empty
        rows_list, labels = [], []
        for _ in range(40):
            lo = rng.choice(win - 1, size=2, replace=False)
            hi = rng.choice(win - 1, size=2, replace=False) + 2 * win
            ix = lo.tolist() + hi.tolist()
            vs = rng.normal(size=4).tolist()
            labels.append(float(rng.uniform() > 0.5))
            rows_list.append((ix, vs))
        batch = make_sparse_batch(rows_list, labels)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        obj = GLMObjective(LOGISTIC, d)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v1, g1 = tobj.value_and_gradient(w, tb, 0.0)
        v0, g0 = obj.value_and_gradient(w, batch, 0.0)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-4)
        # middle window: identically zero
        assert np.all(np.asarray(g1[win : 2 * win]) == 0.0)

    def test_all_entries_dropped(self, rng):
        """Weight-0 rows drop every entry; the schedule must still cover
        all output blocks instead of crashing on an empty entry set."""
        batch = make_sparse_batch(
            [([0, 1], [1.0, 2.0]), ([2], [3.0])],
            [1.0, 0.0],
            weights=np.zeros(2),
        )
        d = 5
        tb = tiled_batch_from_sparse(batch, d, params=TileParams(4, 4, 8))
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v, g = tobj.value_and_gradient(w, tb, 0.0)
        assert float(v) == 0.0
        assert np.all(np.asarray(g) == 0.0)


class TestSpill:
    """Spill-to-scatter hybrid (TileParams.spill_cap): tile remainders
    route around the kernel through _Schedule.apply_spill; the combined
    result must stay exact against the scatter objective."""

    def _spilly(self, rng, cap=8):
        batch, d = random_problem(rng, n=160, d=90, k=5)
        params = TileParams(s_hi=8, s_lo=8, chunk=32, spill_cap=cap)
        tb = tiled_batch_from_sparse(batch, d, params=params)
        return batch, tb, d

    def test_spills_present_and_exact(self, rng):
        batch, tb, d = self._spilly(rng)
        assert int(np.count_nonzero(tb.z_sched.spill_vals)) > 0
        assert int(np.count_nonzero(tb.g_sched.spill_vals)) > 0
        obj = GLMObjective(LOGISTIC, d)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = obj.value_and_gradient(w, batch, 0.2)
        v1, g1 = tobj.value_and_gradient(w, tb, 0.2)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(tobj.hessian_diagonal(w, tb, 0.1)),
            np.asarray(obj.hessian_diagonal(w, batch, 0.1)),
            atol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(tobj.hessian_vector(w, w * 0.5, tb, 0.1)),
            np.asarray(obj.hessian_vector(w, w * 0.5, batch, 0.1)),
            atol=2e-4,
        )

    def test_bucketed_tail_is_exact_and_shared_by_near_equal_tails(self, rng):
        """bucket_spill pads each tail to a 16th-32nd of its length: tails
        a few hundred entries apart (the cd cell's 208,000 and 208,768,
        same rows in another order) get one shape, and the objective reads
        the same to the bit (padding slots add val 0 at coordinate 0)."""
        from photon_ml_tpu.ops.tiled_sparse import bucket_spill

        batch, tb, d = self._spilly(rng)

        def with_tail(length):
            def grown(sched):
                extra = length - sched.spill_vals.shape[0]
                return sched._replace(
                    spill_out=jnp.pad(sched.spill_out, (0, extra)),
                    spill_in=jnp.pad(sched.spill_in, (0, extra)),
                    spill_vals=jnp.pad(sched.spill_vals, (0, extra)),
                )
            return tb._replace(
                z_sched=grown(tb.z_sched), g_sched=grown(tb.g_sched)
            )

        bucketed = [bucket_spill(with_tail(n)) for n in (208000, 208768)]
        for sched in ("z_sched", "g_sched"):
            shapes = {
                tuple(a.shape for a in getattr(b, sched)[-3:])
                for b in bucketed
            }
            assert shapes == {((212992,),) * 3}
        # short tails keep their lane multiple; a mesh layout is left alone
        assert bucket_spill(tb).z_sched.spill_vals.shape == (
            tb.z_sched.spill_vals.shape
        )
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu="highest")
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        for f in (tobj.value_and_gradient, tobj.hessian_diagonal):
            for want, got in zip(
                jax.tree.leaves(f(w, tb, 0.2)),
                jax.tree.leaves(f(w, bucketed[0], 0.2)),
            ):
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_spill_reduces_steps(self, rng):
        batch, d = random_problem(rng, n=160, d=90, k=5)
        no_spill = tiled_batch_from_sparse(
            batch, d, params=TileParams(s_hi=8, s_lo=8, chunk=32, spill_cap=0)
        )
        spill = tiled_batch_from_sparse(
            batch, d, params=TileParams(s_hi=8, s_lo=8, chunk=32, spill_cap=8)
        )
        assert spill.z_sched.num_steps < no_spill.z_sched.num_steps
        assert int(np.count_nonzero(no_spill.z_sched.spill_vals)) == 0

    def test_native_matches_numpy_builder(self, rng):
        from photon_ml_tpu.ops import tiled_sparse as ts

        if not ts._tile_lib():
            pytest.skip("native tile builder unavailable")
        n, d, nnz = 400, 260, 5000
        rows = rng.integers(0, n, nnz).astype(np.int64)
        feats = rng.integers(0, d, nnz).astype(np.int64)
        vals = rng.normal(size=nnz).astype(np.float32)
        params = TileParams(s_hi=8, s_lo=8, chunk=32, spill_cap=8)
        win = params.window
        nob = (n + win - 1) // win
        for by_feat, blocks in ((False, nob), (True, (d + win - 1) // win)):
            native = ts._build_schedule_native(
                rows, feats, vals, params=params,
                sort_by_feature_block=by_feat, num_out_blocks=blocks,
            )
            assert native is not None
            saved = ts._tile_lib_handle
            ts._tile_lib_handle = False
            try:
                pyb = ts._build_schedule_np(
                    rows, feats, vals, params=params,
                    sort_by_feature_block=by_feat, num_out_blocks=blocks,
                )
            finally:
                ts._tile_lib_handle = saved
            assert int(np.count_nonzero(native[8])) > 0  # spill exercised
            for a, b in zip(native, pyb):
                np.testing.assert_array_equal(a, b)


class TestWideMxuVariant:
    """mxu="bf16x2w": fused full-width matmuls must match the scatter
    oracle as the f32 "highest" variant does."""

    def test_matches_oracle_and_highest(self, rng):
        from photon_ml_tpu.data.batch import SparseBatch

        n, k, d = 96, 6, 130
        indices = rng.integers(0, d, size=(n, k)).astype(np.int32)
        values = rng.normal(size=(n, k)).astype(np.float32)
        labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
        batch = SparseBatch(
            indices=jnp.asarray(indices), values=jnp.asarray(values),
            labels=jnp.asarray(labels), offsets=jnp.zeros(n),
            weights=jnp.ones(n),
        )
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.5)
        oobj = GLMObjective(LOGISTIC, d)
        v0, g0 = oobj.value_and_gradient(w, batch, 0.1)
        for mxu in ("highest", "bf16x2w"):
            tobj = TiledGLMObjective(LOGISTIC, d, interpret=True, mxu=mxu)
            v1, g1 = tobj.value_and_gradient(w, tb, 0.1)
            assert abs(float(v1 - v0)) / abs(float(v0)) < 1e-4
            assert (
                float(jnp.linalg.norm(g1 - g0) / jnp.linalg.norm(g0)) < 1e-4
            )
            hv0 = oobj.hessian_vector(w, w * 0.3, batch, 0.1)
            hv1 = tobj.hessian_vector(w, w * 0.3, tb, 0.1)
            assert (
                float(jnp.linalg.norm(hv1 - hv0) / jnp.linalg.norm(hv0))
                < 1e-4
            )
