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
        # every nonzero entry appears exactly once in each pass: chunk
        # slots + spill tail + the dense side array (the intercept's)
        nnz = int(np.count_nonzero(np.asarray(batch.values)))
        dense = np.count_nonzero(tb.dense_vals)
        assert dense == np.count_nonzero(np.asarray(batch.weights))
        for sched in (tb.z_sched, tb.g_sched):
            assert (
                np.count_nonzero(sched.vals)
                + np.count_nonzero(sched.spill_vals)
                + dense
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


def _coo_problem(rng, n=150, d=200, k=5, dense=(), padding=9):
    """A SparseBatch of ``n`` rows, the last ``padding`` of them weight 0
    (built out), ``k`` random entries a row over [0, d - 16) and one entry
    a LIVE row in each column of ``dense``."""
    from photon_ml_tpu.data.batch import SparseBatch

    dense = list(dense)
    indices = np.zeros((n, k + len(dense)), np.int32)
    for i in range(n):
        indices[i, :k] = rng.choice(d - 16, size=k, replace=False)
    indices[:, k:] = dense
    values = rng.normal(size=indices.shape).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, n).astype(np.float32)
    if padding:
        weights[-padding:] = 0.0
    return SparseBatch(
        indices=jnp.asarray(indices), values=jnp.asarray(values),
        labels=jnp.asarray((rng.uniform(size=n) > 0.5).astype(np.float32)),
        offsets=jnp.asarray(rng.normal(size=n).astype(np.float32)),
        weights=jnp.asarray(weights),
    )


def _tiled_columns(tb):
    """The feature ids a batch's g-schedule (kernel steps + spill) holds."""
    win = tb.params.window
    g = tb.g_sched
    steps = g.step_out.shape[0]
    ids = np.asarray(g.step_out)[:, None] * win + np.asarray(g.out_pos)[:steps]
    held = set(ids[np.asarray(g.vals)[:steps] != 0].tolist())
    return held | set(
        np.asarray(g.spill_out)[np.asarray(g.spill_vals) != 0].tolist()
    )


class TestDenseColumns:
    """A column with an entry in every live row leaves the tile schedule
    and is applied in float32 beside the kernel (ops/tiled_sparse's module
    docstring)."""

    def test_the_builder_splits_a_column_in_every_live_row(self, rng):
        d = 200
        batch = _coo_problem(rng, d=d, dense=(d - 1, d - 7))
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        live = np.asarray(batch.weights) > 0
        assert tb.dense_cols.tolist() == [d - 7, d - 1]
        assert tb.dense_vals.shape == (2, tb.num_rows)
        assert tb.dense_vals.dtype == jnp.float32
        values = np.asarray(batch.values)
        for k, slot in ((0, -1), (1, -2)):
            want = np.zeros(tb.num_rows, np.float32)
            want[: len(live)] = np.where(live, values[:, slot], 0.0)
            np.testing.assert_array_equal(np.asarray(tb.dense_vals[k]), want)
        assert not _tiled_columns(tb) & {d - 1, d - 7}

    @pytest.mark.parametrize("order", ["row_major", "shuffled"])
    def test_the_split_zeroes_the_columns_entries_and_no_other(self, rng, order):
        """``_split_dense_columns`` against the plain mask, in whatever
        order the COO triple comes: the dense entries' values become 0
        (no entry, to the schedule builders) in a COPY; with nothing
        dense the values come back as the same object."""
        from photon_ml_tpu.ops.tiled_sparse import _split_dense_columns

        n, k, d = 5000, 7, 3000
        feats = rng.integers(0, d - 1, size=(n, k)).astype(np.int64)
        feats[:, 3] = d - 1
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        feats = feats.reshape(-1)
        vals = rng.normal(size=n * k).astype(np.float32)
        if order == "shuffled":
            perm = rng.permutation(n * k)
            rows, feats, vals = rows[perm], feats[perm], vals[perm]
        before = vals.copy()
        out, cols, dense = _split_dense_columns(rows, feats, vals, n, d, 8)
        np.testing.assert_array_equal(vals, before)  # the caller's: untouched
        column = feats == d - 1
        assert cols.tolist() == [d - 1] and dense.shape == (1, n)
        np.testing.assert_array_equal(out, np.where(column, 0, vals))
        want = np.zeros(n, np.float32)
        want[rows[column]] = vals[column]
        np.testing.assert_array_equal(dense[0], want)
        feats[np.flatnonzero(column)[0]] = 0  # one row short: nothing dense
        again, cols, dense = _split_dense_columns(rows, feats, vals, n, d, 8)
        assert again is vals and cols.shape == (0,) and dense.shape == (0, n)

    def test_a_zero_value_is_no_entry_to_either_schedule_builder(self, rng):
        """The native builder and its numpy oracle pass over an entry
        whose value is 0: both build what they build of the compacted
        triple, array for array."""
        from photon_ml_tpu.ops import tiled_sparse as ts

        n, d, nnz = 400, 260, 5000
        rows = rng.integers(0, n, nnz).astype(np.int64)
        feats = rng.integers(0, d, nnz).astype(np.int64)
        vals = rng.normal(size=nnz).astype(np.float32)
        vals[rng.uniform(size=nnz) < 0.2] = 0.0
        live = vals != 0
        params = TileParams(s_hi=8, s_lo=8, chunk=32, spill_cap=8)
        kw = dict(
            params=params, sort_by_feature_block=True,
            num_out_blocks=(d + 63) // 64,
        )
        want = ts._build_schedule_np(rows[live], feats[live], vals[live], **kw)
        native = ts._build_schedule_native(rows, feats, vals, **kw)
        assert native is not None
        saved = ts._tile_lib_handle
        ts._tile_lib_handle = False
        try:
            oracle = ts._build_schedule_np(rows, feats, vals, **kw)
        finally:
            ts._tile_lib_handle = saved
        for a, b, c in zip(want, native, oracle):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    @pytest.mark.parametrize("fault", ["misses_a_row", "twice_in_a_row"])
    def test_a_column_short_of_a_live_row_stays_tiled(self, rng, fault):
        """One live row without the column, or (the same entry count) the
        column twice in one row and missing in another."""
        d = 200
        batch = _coo_problem(rng, d=d, dense=(d - 1,))
        indices = np.asarray(batch.indices).copy()
        indices[3, -1] = indices[4, 0] if fault == "twice_in_a_row" else 0
        if fault == "twice_in_a_row":
            indices[4, 0] = d - 1
        batch = batch._replace(indices=jnp.asarray(indices))
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        assert tb.dense_cols is None and tb.dense_vals is None
        assert d - 1 in _tiled_columns(tb)

    def test_no_dense_column_builds_the_schedules_as_before(self, rng):
        """K = 0: the batch is the pytree it was, and its schedules are,
        byte for byte, what the schedule builder makes of the whole COO
        triple under the parameters resolved from all of it."""
        from photon_ml_tpu.ops import tiled_sparse as ts

        d = 200
        batch = _coo_problem(rng, d=d)
        tb = tiled_batch_from_sparse(batch, d, params=TileParams(8, 8))
        assert tb.dense_cols is None and tb.dense_vals is None
        assert len(jax.tree.leaves(tb)) == 22
        rows, feats, vals, _ = ts._sparse_coo(batch)
        blocks = tb.num_row_blocks, tb.num_feat_blocks
        params = TileParams(8, 8).resolved(len(vals), blocks[0] * blocks[1])
        assert params == tb.params
        for sched, by_feat, out_blocks in (
            (tb.z_sched, False, blocks[0]), (tb.g_sched, True, blocks[1]),
        ):
            want = ts._build_schedule_np(
                rows, feats, vals, params=params,
                sort_by_feature_block=by_feat, num_out_blocks=out_blocks,
            )
            for got, arr in zip(sched, want):
                assert np.asarray(got).tobytes() == arr.tobytes()
        again = tiled_batch_from_sparse(
            batch, d, params=TileParams(8, 8), max_dense_columns=0
        )
        assert jax.tree.structure(again) == jax.tree.structure(tb)

    def test_the_bound_leaves_a_further_dense_column_tiled(self, rng):
        from photon_ml_tpu.ops.tiled_sparse import MAX_DENSE_COLUMNS

        d = 200
        dense = tuple(range(d - 10, d))
        batch = _coo_problem(rng, d=d, dense=dense)
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        assert MAX_DENSE_COLUMNS == 8
        assert tb.dense_cols.tolist() == list(dense[:8])
        assert _tiled_columns(tb) & set(dense) == {d - 2, d - 1}
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v0, g0 = GLMObjective(LOGISTIC, d).value_and_gradient(w, batch, 0.1)
        v1, g1 = TiledGLMObjective(
            LOGISTIC, d, interpret=True
        ).value_and_gradient(w, tb, 0.1)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(g1), np.asarray(g0), atol=1e-4 * float(jnp.max(jnp.abs(g0)))
        )

    @pytest.mark.parametrize(
        "method",
        ["value_and_gradient", "scores", "hessian_vector", "hessian_diagonal"],
    )
    def test_the_default_variant_matches_the_scatter_objective(
        self, rng, method
    ):
        """Every method inherits the side term from the two passes: at
        "bf16x2w", with a normalisation shift and factor, offsets and
        weight-0 rows, two dense columns."""
        from photon_ml_tpu.models.glm import compute_scores
        from photon_ml_tpu.ops.normalization import NormalizationContext

        d = 200
        batch = _coo_problem(rng, d=d, dense=(d - 1, 11))
        ctx = NormalizationContext(
            factor=jnp.asarray(rng.uniform(0.5, 2.0, d).astype(np.float32)),
            shift=jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1),
        )
        obj = GLMObjective(LOGISTIC, d, ctx)
        tobj = TiledGLMObjective(LOGISTIC, d, norm=ctx, interpret=True)
        assert tobj.mxu == "bf16x2w"
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        assert tb.dense_cols.tolist() == [11, d - 1]
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        u = jnp.asarray(rng.normal(size=d).astype(np.float32))
        if method == "value_and_gradient":
            want, got = (
                o.value_and_gradient(w, b, 0.2) for o, b in ((obj, batch), (tobj, tb))
            )
            np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
            want, got = want[1], got[1]
        elif method == "scores":
            # (original-space coefficients; a built-out row scores 0)
            live = np.asarray(batch.weights) > 0
            want = np.where(live, np.asarray(compute_scores(w, batch)), 0.0)
            got = tobj.scores(w, tb)[: len(live)]
        elif method == "hessian_vector":
            want = obj.hessian_vector(w, u, batch, 0.2)
            got = tobj.hessian_vector(w, u, tb, 0.2)
        else:
            want = obj.hessian_diagonal(w, batch, 0.1)
            got = tobj.hessian_diagonal(w, tb, 0.1)
        scale = float(np.max(np.abs(np.asarray(want))))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4 * scale
        )

    def test_a_coefficient_bank_under_vmap(self, rng):
        """The batched lambda grid's path: the kernel's ``custom_vmap``
        rule takes the bank, the side term batches as plain jax.numpy."""
        d = 200
        batch = _coo_problem(rng, d=d, dense=(d - 1,))
        tb = tiled_batch_from_sparse(batch, d, params=PARAMS)
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True)
        bank = jnp.asarray(rng.normal(size=(3, d)).astype(np.float32))
        l2 = jnp.asarray([0.0, 0.1, 1.0], jnp.float32)
        values, grads = jax.vmap(
            lambda w, l: tobj.value_and_gradient(w, tb, l)
        )(bank, l2)
        obj = GLMObjective(LOGISTIC, d)
        for i in range(3):
            v0, g0 = obj.value_and_gradient(bank[i], batch, l2[i])
            np.testing.assert_allclose(float(values[i]), float(v0), rtol=1e-5)
            np.testing.assert_allclose(
                np.asarray(grads[i]), np.asarray(g0),
                atol=1e-4 * float(jnp.max(jnp.abs(g0))),
            )

    def test_a_tiled_in_intercept_shifts_every_margin_the_same_way(self, rng):
        """What the side term is for (PERF.md section 6, PR 28 and 32): a
        bfloat16 hi+lo pair does not hold 3.7182817, and every row reads
        it, so with the intercept IN the tiles (the builder's bound at 0)
        the margins' MEAN error against a float64 evaluation is the
        coefficient's rounding (the pair holds 3.7182817 - 9.54e-7),
        where any other column's averages out. With the column out the
        mean error is float32's."""
        n, d, k = 2048, 200, 5
        batch = _coo_problem(rng, n=n, d=d, k=k, dense=(d - 1,), padding=0)
        values = np.asarray(batch.values).copy()
        values[:, -1] = 1.0
        batch = batch._replace(
            values=jnp.asarray(values), offsets=jnp.zeros(n, jnp.float32)
        )
        w = rng.normal(size=d).astype(np.float32) * 0.1
        w[d - 1] = np.float32(3.7182817)
        exact = np.einsum(
            "nk,nk->n", values.astype(np.float64),
            w.astype(np.float64)[np.asarray(batch.indices)],
        )
        tobj = TiledGLMObjective(LOGISTIC, d, interpret=True)

        def mean_error(**kw):
            tb = tiled_batch_from_sparse(batch, d, params=PARAMS, **kw)
            z = np.asarray(tobj.margins(jnp.asarray(w), tb))[:n]
            return abs(float(np.mean(z.astype(np.float64) - exact)))

        assert mean_error() < 1e-7
        assert mean_error(max_dense_columns=0) > 8e-7

    def test_the_counter_and_the_span_say_it_engaged(self, rng):
        from photon_ml_tpu.obs import trace as obs_trace
        from photon_ml_tpu.obs.registry import default_registry

        d = 200
        batch = _coo_problem(rng, d=d, dense=(d - 1,))
        counter = default_registry().counter("photon_tiled_entries_total")
        paths = ("kernel", "spill", "dense")
        before = [counter.value(path=p) for p in paths]
        with obs_trace.tracing_scope(True):
            obs_trace.tracer().clear()
            tb = tiled_batch_from_sparse(
                batch, d, params=TileParams(8, 8, 32, spill_cap=8)
            )
            spans = [
                s for s in obs_trace.tracer().drain()
                if s.name == "tiled.schedule_build"
            ]
        assert [s.attrs["dense_columns"] for s in spans] == [1, 1]
        kernel, spill, dense = (
            counter.value(path=p) - b for p, b in zip(paths, before)
        )
        live = int(np.count_nonzero(np.asarray(batch.weights)))
        # two schedules a batch, each applying every entry once
        assert dense == 2 * live and spill > 0
        assert kernel + spill == 2 * 5 * live
        assert spill == sum(
            np.count_nonzero(s.spill_vals) for s in (tb.z_sched, tb.g_sched)
        )
