"""Kernel checks: interpret-mode Pallas vs oracles AND vs the engine itself.

The spmv sweeps keep the isolated shape/dtype coverage; the fused-round
checks are engine-integration tests — the kernel consumes a real
:class:`repro.core.engine.DeviceSchedule` built from a real graph and must
match the engine's XLA round bit-for-bit (the contract ``backend="pallas"``
stands on; see ``tests/test_pallas_backend.py`` for the full solver matrix).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import make_schedule, round_fn
from repro.core.semiring import INT_INF, MIN_PLUS, PLUS_TIMES
from repro.graphs.generators import make_graph
from repro.kernels import ref
from repro.kernels.ops import ell_from_csr, fused_round, spmv
from repro.kernels.round_block import fused_round_fn, resolve_interpret
from repro.kernels.spmv_ell import spmv_ell


def _ell(rng, rows, max_deg, n_slots, dtype, pad_val):
    idx = rng.integers(0, n_slots - 1, (rows, max_deg)).astype(np.int32)
    if dtype == np.float32:
        val = (rng.random((rows, max_deg)) * 0.1).astype(dtype)
    else:
        val = rng.integers(1, 200, (rows, max_deg)).astype(dtype)
    # sprinkle padding entries
    mask = rng.random((rows, max_deg)) < 0.3
    val[mask] = pad_val
    return idx, val


@pytest.mark.parametrize("rows", [8, 64, 256])
@pytest.mark.parametrize("max_deg", [1, 7, 128])
def test_spmv_plus_times_shapes(rng, rows, max_deg):
    n = 500
    idx, val = _ell(rng, rows, max_deg, n, np.float32, 0.0)
    x = rng.random(n + 1).astype(np.float32)
    out_k = spmv_ell(
        jnp.asarray(x),
        jnp.asarray(idx),
        jnp.asarray(val),
        semiring="plus_times",
        row_tile=min(8, rows),
    )
    out_r = ref.spmv_ell_ref(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(val), "plus_times"
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5)


@pytest.mark.parametrize("rows", [8, 128])
@pytest.mark.parametrize("max_deg", [3, 64])
def test_spmv_min_plus_shapes(rng, rows, max_deg):
    n = 300
    idx, val = _ell(rng, rows, max_deg, n, np.int32, INT_INF)
    x = rng.integers(0, 1000, n + 1).astype(np.int32)
    x[rng.random(n + 1) < 0.5] = INT_INF
    out_k = spmv_ell(
        jnp.asarray(x),
        jnp.asarray(idx),
        jnp.asarray(val),
        semiring="min_plus",
        row_tile=min(8, rows),
    )
    out_r = ref.spmv_ell_ref(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(val), "min_plus"
    )
    assert (np.asarray(out_k) == np.asarray(out_r)).all()


def test_spmv_on_real_graph(rng):
    g = make_graph("web", scale=9, efactor=8, kind="pagerank")
    idx, val = ell_from_csr(g)
    pad = (-len(idx)) % 256
    idx = np.pad(idx, ((0, pad), (0, 0)))
    val = np.pad(val, ((0, pad), (0, 0)))
    x = rng.random(g.n + 1).astype(np.float32)
    out_k = spmv(jnp.asarray(x), jnp.asarray(idx), jnp.asarray(val), "plus_times")
    out_r = ref.spmv_ell_ref(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(val), "plus_times"
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5)


def test_interpret_auto_dispatch_is_backend_aware():
    """``interpret=None`` interprets off-TPU and compiles on TPU; explicit
    booleans are honoured (the old ``interpret=True`` default silently
    interpreted on TPU when called directly)."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


class TestEllFromCsr:
    """The vectorized layout builder (no per-row Python loop)."""

    def test_matches_loop_reference(self, rng):
        g = make_graph("kron", scale=8, efactor=8, kind="pagerank")
        idx, val = ell_from_csr(g, lane_pad=8)
        degs = np.diff(g.indptr)
        assert idx.shape == val.shape == (g.n, -(-int(degs.max()) // 8) * 8)
        for r in [0, 1, int(degs.argmax()), g.n - 1]:  # spot-check rows
            e0, e1 = g.indptr[r], g.indptr[r + 1]
            np.testing.assert_array_equal(idx[r, : e1 - e0], g.indices[e0:e1])
            np.testing.assert_array_equal(val[r, : e1 - e0], g.values[e0:e1])
            assert (val[r, e1 - e0 :] == 0.0).all()  # plus-times annihilator

    def test_rows_slice_and_int_padding(self):
        g = make_graph("kron", scale=8, efactor=8, kind="sssp")
        rows = np.asarray([3, 0, 17])
        idx, val = ell_from_csr(g, rows_slice=rows, lane_pad=4)
        assert idx.shape[0] == 3
        for i, r in enumerate(rows):
            e0, e1 = g.indptr[r], g.indptr[r + 1]
            np.testing.assert_array_equal(idx[i, : e1 - e0], g.indices[e0:e1])
            assert (val[i, e1 - e0 :] == INT_INF).all()  # min-plus annihilator

    def test_ell_reduction_matches_graph_spmv(self, rng):
        """ELL built by fancy indexing computes the same pull reduction as
        the CSR definition — end-to-end layout correctness."""
        g = make_graph("web", scale=8, efactor=8, kind="pagerank")
        idx, val = ell_from_csr(g, lane_pad=8)
        x = rng.random(g.n + 1).astype(np.float32)
        out = np.asarray(ref.spmv_ell_ref(jnp.asarray(x), idx, val, "plus_times"))
        expect = np.zeros(g.n, np.float32)
        for u in range(g.n):
            e0, e1 = g.indptr[u], g.indptr[u + 1]
            expect[u] = np.sum(x[g.indices[e0:e1]] * g.values[e0:e1])
        np.testing.assert_allclose(out, expect, rtol=1e-5)


class TestFusedRoundEngineIntegration:
    """round_block vs the engine's XLA round on real schedules."""

    def _x_ext(self, g, sr, rng):
        if sr is MIN_PLUS:
            x0 = rng.integers(0, 1000, g.n).astype(np.int32)
        else:
            x0 = rng.random(g.n).astype(np.float32)
        return jnp.concatenate([jnp.asarray(x0), jnp.asarray([sr.zero], sr.dtype)])

    @pytest.mark.parametrize("delta", [16, 64, 10_000])
    def test_pagerank_round_bit_identical(self, rng, delta):
        g = make_graph("twitter", scale=9, efactor=8, kind="pagerank")
        sched = make_schedule(g, 4, delta, PLUS_TIMES, min_chunk=8)
        tele = np.float32(0.15 / g.n)
        row_update = lambda o, r, w: tele + r
        x = self._x_ext(g, PLUS_TIMES, rng)
        x_ref = np.asarray(round_fn(sched, PLUS_TIMES, row_update)(x))
        x_pal = np.asarray(fused_round(x, sched, PLUS_TIMES, row_update))
        np.testing.assert_array_equal(x_ref[:-1], x_pal[:-1])

    def test_min_plus_round_bit_identical(self, rng):
        g = make_graph("kron", scale=8, efactor=8, kind="sssp")
        sched = make_schedule(g, 4, 32, MIN_PLUS)
        row_update = lambda o, r, w: jnp.minimum(o, r)
        x = self._x_ext(g, MIN_PLUS, rng)
        x_ref = np.asarray(round_fn(sched, MIN_PLUS, row_update)(x))
        x_pal = np.asarray(fused_round(x, sched, MIN_PLUS, row_update))
        np.testing.assert_array_equal(x_ref[:-1], x_pal[:-1])

    def test_kernel_matches_pure_jnp_oracle(self, rng):
        g = make_graph("kron", scale=8, efactor=8, kind="pagerank")
        sched = make_schedule(g, 4, 32, PLUS_TIMES)
        tele = np.float32(0.15 / g.n)
        row_update = lambda o, r, w: tele + r
        x = self._x_ext(g, PLUS_TIMES, rng)
        out_k = fused_round(x, sched, PLUS_TIMES, row_update, use_kernel=True)
        out_r = fused_round(x, sched, PLUS_TIMES, row_update, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(out_k)[:-1], np.asarray(out_r)[:-1])

    def test_fused_round_is_gauss_seidel_not_jacobi(self, rng):
        """Later commit steps must see earlier commits (the whole point of
        the fusion): with S > 1 the fused round differs from applying every
        commit step against the frozen round-start frontier."""
        g = make_graph("twitter", scale=9, efactor=8, kind="pagerank")
        sched = make_schedule(g, 4, 32, PLUS_TIMES, min_chunk=8)
        assert sched.S > 1
        tele = np.float32(0.15 / g.n)
        row_update = lambda o, r, w: tele + r
        x = self._x_ext(g, PLUS_TIMES, rng)
        out_gs = np.asarray(fused_round(x, sched, PLUS_TIMES, row_update))
        # Jacobi variant: every step's reduction reads the original frontier
        x_j = x
        for s in range(sched.S):
            contrib = PLUS_TIMES.mul(x[sched.src[s]], sched.val[s])
            seg = (
                sched.dst_local[s]
                + (jnp.arange(sched.P, dtype=jnp.int32) * (sched.delta + 1))[:, None]
            )
            red = jax.ops.segment_sum(
                contrib.reshape(-1),
                seg.reshape(-1),
                num_segments=sched.P * (sched.delta + 1),
            ).reshape(sched.P, sched.delta + 1)[:, : sched.delta]
            new = tele + red
            x_j = x_j.at[sched.rows[s].reshape(-1)].set(new.reshape(-1), mode="drop")
        assert np.abs(out_gs[:-1] - np.asarray(x_j)[:-1]).max() > 1e-6

    def test_query_round_via_ops(self, rng):
        g = make_graph("twitter", scale=9, efactor=8, kind="pagerank")
        sched = make_schedule(g, 4, 48, PLUS_TIMES, min_chunk=8)
        row_update_q = lambda o, r, w, q: q[w] + r
        q = jnp.asarray(rng.random(g.n).astype(np.float32))
        x = self._x_ext(g, PLUS_TIMES, rng)
        out_k = fused_round(x, sched, PLUS_TIMES, row_update_q, q=q)
        out_r = fused_round(x, sched, PLUS_TIMES, row_update_q, q=q, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(out_k)[:-1], np.asarray(out_r)[:-1])

    def test_sync_schedule_single_kernel_step(self, rng):
        """S == 1 (sync): one commit step, still one fused kernel — exact
        Jacobi, matching the engine."""
        g = make_graph("kron", scale=8, efactor=8, kind="pagerank")
        sched = make_schedule(g, 4, None, PLUS_TIMES, mode="sync")
        assert sched.S == 1
        tele = np.float32(0.15 / g.n)
        row_update = lambda o, r, w: tele + r
        x = self._x_ext(g, PLUS_TIMES, rng)
        x_ref = np.asarray(round_fn(sched, PLUS_TIMES, row_update)(x))
        x_pal = np.asarray(
            fused_round_fn(sched, PLUS_TIMES, row_update, interpret=True)(x)
        )
        np.testing.assert_array_equal(x_ref[:-1], x_pal[:-1])
