"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus integration with the CPAA solver."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.graph import generators
from repro.graph.structure import build_block_ell
from repro.kernels.bsr_spmm.ops import bsr_spmm
from repro.kernels.bsr_spmm.ref import bsr_spmm_ref
from repro.kernels.cheb_step.ops import cheb_step
from repro.kernels.cheb_step.ref import cheb_step_ref
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


class TestBsrSpmm:
    @pytest.mark.parametrize("block", [8, 32, 128])
    @pytest.mark.parametrize("bt", [1, 8, 128])
    def test_shapes_vs_ref(self, block, bt):
        g = generators.erdos_renyi(max(3 * block, 200), 5.0, seed=block + bt)
        be = build_block_ell(g, block=block)
        x = jax.random.normal(jax.random.PRNGKey(0), (be.n, bt), jnp.float32)
        y_k = bsr_spmm(jnp.asarray(be.block_cols), jnp.asarray(be.values), x,
                       use_kernel=True, interpret=True)
        y_r = bsr_spmm_ref(jnp.asarray(be.block_cols), jnp.asarray(be.values), x)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("max_prefetch", [1, 7, 24, 1 << 16])
    def test_split_prefetch_vs_ref(self, max_prefetch):
        """Row blocks split over several calls (one per SMEM-sized slice of
        the flat slot index) give the single-call answer."""
        from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_pallas
        g = generators.erdos_renyi(300, 5.0, seed=max_prefetch)
        be = build_block_ell(g, block=32)
        x = jax.random.normal(jax.random.PRNGKey(4), (be.n, 8), jnp.float32)
        cols, vals = jnp.asarray(be.block_cols), jnp.asarray(be.values)
        y_k = bsr_spmm_pallas(cols, vals, x, interpret=True,
                              max_prefetch=max_prefetch)
        np.testing.assert_allclose(np.asarray(y_k),
                                   np.asarray(bsr_spmm_ref(cols, vals, x)),
                                   rtol=1e-5, atol=1e-5)

    def test_vector_input_squeeze(self):
        g = generators.tri_mesh(8, 9)
        be = build_block_ell(g, block=16)
        x = jax.random.normal(jax.random.PRNGKey(1), (be.n,), jnp.float32)
        y = bsr_spmm(jnp.asarray(be.block_cols), jnp.asarray(be.values), x,
                     use_kernel=True, interpret=True)
        assert y.shape == (be.n,)

    def test_matches_coo_spmv(self):
        """Kernel result == segment-sum SpMV on the original graph."""
        from repro.graph.ops import device_graph, spmv
        g = generators.tri_mesh(11, 12)
        be = build_block_ell(g, block=32)
        dg = device_graph(g)
        x = jax.random.normal(jax.random.PRNGKey(2), (g.n,), jnp.float32)
        y_coo = spmv(dg, x)
        xp = jnp.zeros((be.n,), jnp.float32).at[:g.n].set(x[jnp.asarray(be.perm)])
        y_blk = bsr_spmm(jnp.asarray(be.block_cols), jnp.asarray(be.values),
                         xp, use_kernel=True, interpret=True)
        y_unperm = jnp.zeros((g.n,), jnp.float32).at[jnp.asarray(be.perm)].set(y_blk[:g.n])
        np.testing.assert_allclose(np.asarray(y_unperm), np.asarray(y_coo),
                                   rtol=2e-4, atol=1e-5)

    def test_bf16_values(self):
        g = generators.erdos_renyi(256, 4.0, seed=7)
        be = build_block_ell(g, block=32)
        vals = jnp.asarray(be.values, jnp.bfloat16).astype(jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(3), (be.n, 4), jnp.float32)
        y_k = bsr_spmm(jnp.asarray(be.block_cols), vals, x,
                       use_kernel=True, interpret=True)
        y_r = bsr_spmm_ref(jnp.asarray(be.block_cols), vals, x)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=1e-2, atol=1e-2)


class TestChebStep:
    @pytest.mark.parametrize("n", [64, 1000, 4096, 10_001])
    @pytest.mark.parametrize("ndim", [1, 2])
    def test_shapes_vs_ref(self, n, ndim):
        shape = (n,) if ndim == 1 else (n, 4)
        ks = jax.random.split(jax.random.PRNGKey(n + ndim), 3)
        y, t, acc = (jax.random.normal(k, shape, jnp.float32) for k in ks)
        tk, ak = cheb_step(y, t, acc, 0.5567, use_kernel=True, interpret=True)
        tr, ar = cheb_step_ref(y, t, acc, jnp.float32(0.5567))
        np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(ak), np.asarray(ar), rtol=1e-5,
                                   atol=1e-6)

    @given(st.integers(min_value=1, max_value=2000),
           st.floats(min_value=-2.0, max_value=2.0))
    @settings(max_examples=20, deadline=None)
    def test_property_random_sizes(self, n, ck):
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        y, t, acc = (jax.random.normal(k, (n,), jnp.float32) for k in ks)
        tk, ak = cheb_step(y, t, acc, ck, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(tk), np.asarray(2 * y - t),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ak),
                                   np.asarray(acc + ck * (2 * y - t)),
                                   rtol=1e-4, atol=1e-5)


class TestEmbeddingBag:
    @pytest.mark.parametrize("dim", [8, 64, 128])
    @pytest.mark.parametrize("bag", [1, 4, 26])
    def test_shapes_vs_ref(self, dim, bag):
        v, b = 500, 16
        ks = jax.random.split(jax.random.PRNGKey(dim + bag), 3)
        table = jax.random.normal(ks[0], (v, dim), jnp.float32)
        ids = jax.random.randint(ks[1], (b, bag), 0, v)
        w = jax.random.uniform(ks[2], (b, bag), jnp.float32)
        out_k = embedding_bag(ids, table, w, use_kernel=True, interpret=True)
        out_r = embedding_bag_ref(ids, table, w)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)

    def test_default_weights_sum(self):
        v, d = 50, 8
        table = jnp.arange(v * d, dtype=jnp.float32).reshape(v, d)
        ids = jnp.array([[1, 1, 2], [0, 3, 3]], jnp.int32)
        out = embedding_bag(ids, table, use_kernel=True, interpret=True)
        want = jnp.stack([2 * table[1] + table[2], table[0] + 2 * table[3]])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)

    def test_duplicate_ids_accumulate(self):
        v, d = 20, 16
        table = jax.random.normal(jax.random.PRNGKey(0), (v, d), jnp.float32)
        ids = jnp.full((4, 7), 5, jnp.int32)
        out = embedding_bag(ids, table, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.tile(7 * table[5], (4, 1))),
                                   rtol=1e-5)


class TestKernelSolverIntegration:
    def test_cpaa_with_kernels_matches_reference_solver(self):
        """Full CPAA loop on the block-ELL kernel + fused update == cpaa()."""
        from repro.core import cpaa, make_schedule
        from repro.graph.ops import device_graph
        g = generators.tri_mesh(13, 15)
        sched = make_schedule(0.85, 1e-8)
        pi_ref = np.asarray(cpaa(device_graph(g), schedule=sched).pi, np.float64)

        be = build_block_ell(g, block=32)
        bc = jnp.asarray(be.block_cols)
        vals = jnp.asarray(be.values)
        p = jnp.zeros((be.n,), jnp.float32).at[:g.n].set(1.0)
        coeffs = np.asarray(sched.coeffs, np.float32)
        t_prev = p
        acc = coeffs[0] * t_prev
        t_cur = bsr_spmm(bc, vals, p, use_kernel=True, interpret=True)
        acc = acc + coeffs[1] * t_cur
        for k in range(2, len(coeffs)):
            y = bsr_spmm(bc, vals, t_cur, use_kernel=True, interpret=True)
            t_next, acc = cheb_step(y, t_prev, acc, coeffs[k],
                                    use_kernel=True, interpret=True)
            t_prev, t_cur = t_cur, t_next
        pi = np.asarray(acc, np.float64) / float(np.sum(np.asarray(acc)))
        pi_unperm = np.empty(g.n)
        pi_unperm[be.perm] = pi[:g.n]
        err = np.max(np.abs(pi_unperm - pi_ref) / pi_ref)
        assert err < 1e-4, err
