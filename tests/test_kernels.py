"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


class TestEwmaKernel:
    @pytest.mark.parametrize("b,t", [(1, 64), (3, 300), (8, 1024), (17, 257), (256, 96)])
    @pytest.mark.parametrize("alpha", [0.01, 0.05, 0.2])
    def test_matches_ref(self, b, t, alpha):
        ts = jnp.asarray(RNG.normal(0, 2, (b, t)), jnp.float32)
        m1, v1 = ops.ewma_scan(ts, alpha)
        m2, v2 = ref.ewma_scan_ref(ts, alpha)
        np.testing.assert_allclose(m1, m2, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(v1, v2, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("block_t", [64, 128, 512])
    def test_block_shapes(self, block_t):
        from repro.kernels.ewma import ewma_scan_pallas

        ts = jnp.asarray(RNG.normal(0, 1, (4, 777)), jnp.float32)
        m1, v1 = ewma_scan_pallas(ts, 0.02, block_t=block_t, interpret=True)
        m2, v2 = ref.ewma_scan_ref(ts, 0.02)
        np.testing.assert_allclose(m1, m2, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(v1, v2, rtol=2e-4, atol=2e-4)

    def test_paper_init(self):
        ts = jnp.asarray(RNG.normal(0, 1, (2, 50)), jnp.float32)
        m, v = ops.ewma_scan(ts, 0.02)
        np.testing.assert_allclose(m[:, 0], ts[:, 0], rtol=1e-6)
        np.testing.assert_allclose(v[:, 0], 1.0, rtol=1e-6)

    def test_large_values(self):
        """Chunked rescaling keeps f32 precision for offset streams."""
        ts = jnp.asarray(RNG.normal(1000, 5, (2, 512)), jnp.float32)
        m1, v1 = ops.ewma_scan(ts, 0.05)
        m2, v2 = ref.ewma_scan_ref(ts, 0.05)
        np.testing.assert_allclose(m1, m2, rtol=1e-4)
        np.testing.assert_allclose(v1, v2, rtol=1e-3, atol=1e-2)


class TestKmeansKernel:
    @pytest.mark.parametrize("s,n,d,k", [
        (1, 16, 2, 3), (3, 50, 2, 7), (2, 200, 2, 100), (1, 64, 8, 5),
        (2, 128, 128, 16), (1, 300, 2, 1),
    ])
    def test_matches_ref(self, s, n, d, k):
        x = jnp.asarray(RNG.normal(size=(s, n, d)), jnp.float32)
        mask = jnp.asarray(RNG.random((s, n)) > 0.25, jnp.float32)
        c = jnp.asarray(RNG.normal(size=(s, k, d)), jnp.float32)
        act = jnp.asarray(RNG.random((s, k)) > 0.2, jnp.float32)
        act = act.at[:, 0].set(1.0)  # at least one active center
        l1, s1, c1 = ops.kmeans_assign(x, mask, c, act)
        l2, s2, c2 = ref.kmeans_assign_ref(x, mask, c, act)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(c1, c2, rtol=1e-6)

    def test_block_n_tiling(self):
        from repro.kernels.kmeans import kmeans_assign_pallas

        x = jnp.asarray(RNG.normal(size=(2, 500, 2)), jnp.float32)
        mask = jnp.ones((2, 500), jnp.float32)
        c = jnp.asarray(RNG.normal(size=(2, 10, 2)), jnp.float32)
        act = jnp.ones((2, 10), jnp.float32)
        l1, s1, c1 = kmeans_assign_pallas(x, mask, c, act, block_n=128,
                                          interpret=True)
        l2, s2, c2 = ref.kmeans_assign_ref(x, mask, c, act)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)

    def test_lloyd_step_contract(self):
        """new_centers from (sums, counts) must equal masked means."""
        x = jnp.asarray(RNG.normal(size=(1, 80, 2)), jnp.float32)
        mask = jnp.ones((1, 80), jnp.float32)
        c = jnp.asarray(RNG.normal(size=(1, 4, 2)), jnp.float32)
        act = jnp.ones((1, 4), jnp.float32)
        labels, sums, counts = ops.kmeans_assign(x, mask, c, act)
        for j in range(4):
            sel = np.asarray(labels[0]) == j
            if sel.any():
                np.testing.assert_allclose(
                    np.asarray(sums[0, j] / counts[0, j]),
                    np.asarray(x[0])[sel].mean(0), rtol=1e-4)


class TestDtwKernel:
    @pytest.mark.parametrize("b,n", [(1, 32), (4, 150), (8, 128), (3, 257), (16, 64)])
    def test_matches_ref_full(self, b, n):
        x = jnp.asarray(RNG.normal(size=(b, n)).cumsum(1), jnp.float32)
        y = x + jnp.asarray(RNG.normal(0, 0.3, (b, n)), jnp.float32)
        d1 = ops.dtw(x, y)
        d2 = ref.dtw_batch_ref(x, y)
        np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("band", [5, 20, 64])
    def test_matches_ref_banded(self, band):
        x = jnp.asarray(RNG.normal(size=(4, 200)).cumsum(1), jnp.float32)
        y = x + jnp.asarray(RNG.normal(0, 0.2, (4, 200)), jnp.float32)
        d1 = ops.dtw(x, y, band=band)
        d2 = ref.dtw_batch_ref(x, y, band=band)
        np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)

    def test_identity_zero(self):
        x = jnp.asarray(RNG.normal(size=(3, 90)), jnp.float32)
        np.testing.assert_allclose(ops.dtw(x, x), 0.0, atol=1e-4)

    def test_band_zero_matches_ref(self):
        """Regression: the degenerate band=0 corridor (diagonal-only path)
        must agree between kernel and ref -- and stay finite, not leak the
        _BIG unreachable-cell sentinel."""
        x = jnp.asarray(RNG.normal(size=(3, 96)).cumsum(1), jnp.float32)
        y = x + jnp.asarray(RNG.normal(0, 0.2, (3, 96)), jnp.float32)
        d1 = np.asarray(ops.dtw(x, y, band=0))
        d2 = np.asarray(ref.dtw_batch_ref(x, y, band=0))
        assert (d1 < 1e10).all()
        np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)
        # band=0 == pointwise L2 on equal-length pairs
        eu = np.sqrt(np.sum((np.asarray(x) - np.asarray(y)) ** 2, axis=1))
        np.testing.assert_allclose(d1, eu, rtol=1e-4)

    def test_band_tightens_distance(self):
        """Narrower band restricts warping -> distance monotone non-decreasing."""
        x = jnp.asarray(RNG.normal(size=(2, 100)).cumsum(1), jnp.float32)
        y = jnp.asarray(RNG.normal(size=(2, 100)).cumsum(1), jnp.float32)
        d_full = np.asarray(ops.dtw(x, y))
        d_b10 = np.asarray(ops.dtw(x, y, band=10))
        d_b3 = np.asarray(ops.dtw(x, y, band=3))
        assert (d_b3 >= d_b10 - 1e-4).all()
        assert (d_b10 >= d_full - 1e-4).all()


class TestMaskedKmeansTable:
    """Slot-table Lloyd loop (``core.digitize.masked_kmeans_table``): the
    vmapped reference path must be bitwise-equal to per-slot
    ``masked_kmeans``; the fused-kernel path matches to float tolerance with
    masked labels zeroed (the documented contract that keeps
    ``use_kernel=False`` on bitwise-checked CPU deployments)."""

    def _problem(self, s, n_max, k_max, seed):
        rng = np.random.default_rng(seed)
        coords = jnp.asarray(rng.normal(size=(s, n_max, 2)), jnp.float32)
        n_valid = rng.integers(1, n_max + 1, size=(s,))
        mask = jnp.asarray(np.arange(n_max)[None, :] < n_valid[:, None])
        k = jnp.asarray(rng.integers(1, k_max + 1, size=(s,)), jnp.int32)
        c_init = jnp.asarray(rng.normal(size=(s, k_max, 2)), jnp.float32)
        return coords, mask, c_init, k

    @pytest.mark.parametrize("s,n_max,k_max", [(1, 16, 4), (4, 64, 8), (7, 33, 5)])
    def test_ref_path_bitwise_vs_per_slot(self, s, n_max, k_max):
        from repro.core.digitize import masked_kmeans, masked_kmeans_table

        coords, mask, c_init, k = self._problem(s, n_max, k_max, 11)
        ct, lt = masked_kmeans_table(coords, mask, c_init, k, iters=5)
        cv, lv = jax.vmap(
            lambda co, m, ci, kk: masked_kmeans(co, m, ci, kk, 5)
        )(coords, mask, c_init, k)
        np.testing.assert_array_equal(np.asarray(lt), np.asarray(lv))
        np.testing.assert_array_equal(np.asarray(ct), np.asarray(cv))

    @pytest.mark.parametrize("s,n_max,k_max", [(2, 32, 4), (5, 48, 8)])
    def test_kernel_path_matches_ref(self, s, n_max, k_max):
        from repro.core.digitize import masked_kmeans_table

        coords, mask, c_init, k = self._problem(s, n_max, k_max, 23)
        c_ref, l_ref = masked_kmeans_table(coords, mask, c_init, k, iters=5)
        c_krn, l_krn = masked_kmeans_table(coords, mask, c_init, k, iters=5,
                                           use_kernel=True)
        np.testing.assert_allclose(np.asarray(c_krn), np.asarray(c_ref),
                                   rtol=1e-5, atol=1e-5)
        valid = np.asarray(mask, bool)
        np.testing.assert_array_equal(
            np.asarray(l_krn)[valid], np.asarray(l_ref)[valid])
        assert (np.asarray(l_krn)[~valid] == 0).all()


class TestDigitizeSpanTable:
    """The fused table digitize (``digitize_span_table`` /
    ``digitizer_table_step``) against vmapped per-slot ``digitize_span``:
    bitwise on every DigitizerState leaf and emitted symbol, including
    resumption across split spans (the streaming-cadence shape)."""

    CFGK = dict(tol=0.5, scl=1.0, k_min=3, k_max_active=8, lloyd_iters=5)

    def _table(self, s, n_max, k_max, seed):
        from repro.core.digitize import digitizer_init

        rng = np.random.default_rng(seed)
        keys = jax.random.split(jax.random.key(seed), s)
        state = jax.vmap(lambda kk: digitizer_init(n_max, k_max, kk))(keys)
        lengths = jnp.asarray(rng.integers(1, 9, size=(s, n_max)), jnp.float32)
        incs = jnp.asarray(rng.normal(0, 2, size=(s, n_max)), jnp.float32)
        hi = jnp.asarray(rng.integers(0, n_max + 1, size=(s,)), jnp.int32)
        return state, lengths, incs, hi

    def _assert_state_equal(self, a, b, msg):
        for name in a._fields:
            la, lb = getattr(a, name), getattr(b, name)
            if name == "key":
                la, lb = jax.random.key_data(la), jax.random.key_data(lb)
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=f"{msg}: {name}")

    @pytest.mark.parametrize("s,n_max", [(1, 12), (4, 24), (6, 16)])
    def test_bitwise_vs_vmapped_per_slot(self, s, n_max):
        from repro.core.digitize import digitize_span, digitize_span_table

        state, lengths, incs, hi = self._table(s, n_max, 8, 31 + s)
        lo = jnp.zeros((s,), jnp.int32)
        st_t, sy_t, _ = digitize_span_table(state, lengths, incs, lo, hi,
                                         **self.CFGK)
        st_v, sy_v, _ = jax.vmap(
            lambda st, le, ic, l, h: digitize_span(st, le, ic, l, h,
                                                   **self.CFGK)
        )(state, lengths, incs, lo, hi)
        self._assert_state_equal(st_t, st_v, f"s={s}")
        np.testing.assert_array_equal(np.asarray(sy_t), np.asarray(sy_v))

    def test_split_spans_resume_bitwise(self):
        """Digesting [0, mid) then [mid, hi) must equal one [0, hi) pass --
        per lane, with ragged mids (the arrival-cadence property)."""
        from repro.core.digitize import digitize_span_table

        s, n_max = 5, 20
        state, lengths, incs, hi = self._table(s, n_max, 8, 99)
        rng = np.random.default_rng(7)
        mid = jnp.asarray(
            [int(rng.integers(0, int(h) + 1)) for h in np.asarray(hi)],
            jnp.int32)
        lo = jnp.zeros((s,), jnp.int32)
        st_one, sy_one, _ = digitize_span_table(state, lengths, incs, lo, hi,
                                             **self.CFGK)
        st_a, sy_a, _ = digitize_span_table(state, lengths, incs, lo, mid,
                                         **self.CFGK)
        st_b, sy_b, _ = digitize_span_table(st_a, lengths, incs, mid, hi,
                                         **self.CFGK)
        self._assert_state_equal(st_b, st_one, "split-resume")
        idx = np.arange(n_max)[None, :]
        in_a = idx < np.asarray(mid)[:, None]
        merged = np.where(in_a, np.asarray(sy_a), np.asarray(sy_b))
        np.testing.assert_array_equal(merged, np.asarray(sy_one))
