"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes/dtypes per the brief; tolerances are fp32-accumulation level.
Every kernel call passes ``interpret=True`` explicitly (never via env —
a module-level env var would leak interpret mode into the whole suite).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ridge_prox import batched_affine
from repro.kernels.tv_prox import tv_prox

# hypothesis is optional (shared guard in conftest); the deterministic
# parity sweeps below run regardless, the property tests only with it
from conftest import HAVE_HYPOTHESIS

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st


def rnd(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# tv_prox
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,n", [(7, 2), (512, 2), (1000, 16), (33, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tv_prox_matches_ref(e, n, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    u = rnd(k1, (e, n), dtype, scale=2.0)
    bound = jnp.abs(rnd(k2, (e,), jnp.float32))
    out = tv_prox(u, bound, interpret=True, block_e=64)
    want = ref.tv_prox_ref(u.astype(jnp.float32), bound)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_tv_prox_is_projection():
    """Clipping is idempotent and never increases magnitude."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    u = rnd(k1, (257, 4), scale=3.0)
    bound = jnp.abs(rnd(k2, (257,)))
    once = tv_prox(u, bound, interpret=True)
    twice = tv_prox(once, bound, interpret=True)
    np.testing.assert_allclose(np.asarray(once), np.asarray(twice))
    assert np.all(np.abs(np.asarray(once)) <= np.asarray(bound)[:, None] + 1e-6)


# ---------------------------------------------------------------------------
# batched ridge affine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("v,n", [(300, 2), (64, 8), (1000, 32), (13, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_batched_affine_matches_ref(v, n, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    p = rnd(k1, (v, n, n), dtype)
    x = rnd(k2, (v, n), dtype)
    out = batched_affine(p, x, interpret=True, block_v=64)
    want = ref.batched_affine_ref(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused primal-dual step (interpret kernel vs jnp oracle, shared layout)
# ---------------------------------------------------------------------------
def _fused_step_args(v, n, bv, seed=0, rho=1.9):
    from repro.api.losses import SquaredLoss
    from repro.api.regularizers import TotalVariation
    from repro.core.graph import edge_ends_store, plan_edge_blocks, sbm_graph
    rng = np.random.default_rng(seed)
    g, _ = sbm_graph(rng, (v // 2, v - v // 2), p_in=0.3, p_out=0.03)
    lt = plan_edge_blocks(g, block_nodes=bv)
    kk = jax.random.split(jax.random.PRNGKey(seed), 4)
    ext = (lt.kn - 1) * lt.block_nodes
    pad = lambda a: jnp.pad(a, ((0, ext),) + ((0, 0),) * (a.ndim - 1))
    real = (lt.weights > 0.0).astype(jnp.float32)
    deg = jnp.zeros(lt.nodes_pad).at[lt.src].add(real).at[lt.dst].add(real)
    tau = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 1.0)
    # squared-loss prox params (P, b) in sorted-pkeys order ("b", "p")
    p_win = pad(rnd(kk[2], (lt.nodes_pad, n, n), scale=0.1)
                + jnp.eye(n)[None])
    b_win = pad(rnd(kk[3], (lt.nodes_pad, n), scale=0.1))
    args = (
        pad(rnd(kk[0], (lt.nodes_pad, n))),
        jnp.pad(rnd(kk[1], (lt.edges_pad, n), scale=0.1),
                ((lt.klo * lt.block_edges, lt.khi * lt.block_edges),
                 (0, 0))),
        edge_ends_store(lt.src, lt.dst, lt.klo, lt.khi, lt.block_edges),
        (b_win, p_win),
        pad(tau[:, None]),
        jnp.full((lt.edges_pad, 1), 0.5),
        (1e-2 * lt.weights)[:, None],
    )
    kw = dict(loss=SquaredLoss(), reg=TotalVariation(), pkeys=("b", "p"),
              block_nodes=lt.block_nodes, block_edges=lt.block_edges,
              kn=lt.kn, klo=lt.klo, khi=lt.khi, rho=rho)
    return args, kw


@pytest.mark.parametrize("v,n,bv", [(61, 2, 16), (103, 3, 32), (40, 4, 64)])
@pytest.mark.parametrize("rho", [1.0, 1.9])
def test_fused_pd_step_interpret_matches_ref(v, n, bv, rho):
    from repro.kernels.pd_step import fused_pd_step
    args, kw = _fused_step_args(v, n, bv, seed=v, rho=rho)
    w_k, u_k = fused_pd_step(*args, **kw, interpret=True)
    w_r, u_r = ref.fused_pd_step_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r),
                               rtol=1e-6, atol=1e-6)


def test_fused_pd_step_multi_iteration_equals_repeated_single():
    """Single-block multi-iteration fusion == iterating the single step."""
    from repro.kernels.pd_step import fused_pd_step
    args, kw = _fused_step_args(48, 2, None, seed=4)   # one block
    assert kw["kn"] == 1 and kw["klo"] == 0 and kw["khi"] == 0
    w_m, u_m = fused_pd_step(*args, **kw, iters=5, interpret=True)
    w, u = args[0], args[1]
    for _ in range(5):
        w, u = fused_pd_step(w, u, *args[2:], **kw, interpret=True)
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(u_m), np.asarray(u),
                               rtol=1e-6, atol=1e-6)


def test_force_interpret_has_no_effect_on_tpu(monkeypatch):
    """``REPRO_FORCE_INTERPRET`` moves kernels into interpret mode off-TPU
    only: on a TPU they always compile for the chip."""
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops._use_kernel_default() and not ops._interpret()
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    assert ops._use_kernel_default() and ops._interpret()


# ---------------------------------------------------------------------------
# ops entry points vs ref — odd shapes, dtypes, non-multiple-of-block sizes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,n,block_e", [
    (1, 1, 64),         # degenerate single edge
    (65, 3, 64),        # one past a block boundary
    (127, 2, 32),       # one short of a block boundary
    (96, 5, 32),        # exact multiple, odd feature count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ops_tv_prox_odd_shapes(e, n, block_e, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(10))
    u = rnd(k1, (e, n), dtype, scale=2.0)
    bound = jnp.abs(rnd(k2, (e,), jnp.float32))
    out = ops.tv_prox(u, bound, block_e=block_e)
    want = ref.tv_prox_ref(u.astype(jnp.float32), bound)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("v,n,block_v", [
    (1, 1, 64),
    (65, 3, 64),
    (255, 4, 128),
    (100, 6, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ops_batched_affine_odd_shapes(v, n, block_v, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    p = rnd(k1, (v, n, n), dtype)
    x = rnd(k2, (v, n), dtype)
    out = ops.batched_affine(p, x, block_v=block_v)
    want = ref.batched_affine_ref(p.astype(jnp.float32),
                                  x.astype(jnp.float32))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(e=st.integers(1, 300), n=st.integers(1, 8),
           block_e=st.sampled_from([8, 32, 64, 256]),
           use_bf16=st.booleans(), seed=st.integers(0, 2**31 - 1))
    def test_tv_prox_property_matches_ref(e, n, block_e, use_bf16, seed):
        """ops.tv_prox == ref for arbitrary (E, n), dtype, block size."""
        rng = np.random.default_rng(seed)
        dtype = jnp.bfloat16 if use_bf16 else jnp.float32
        u = jnp.asarray(rng.standard_normal((e, n)) * 3,
                        jnp.float32).astype(dtype)
        bound = jnp.asarray(np.abs(rng.standard_normal(e)), jnp.float32)
        out = ops.tv_prox(u, bound, block_e=block_e)
        want = ref.tv_prox_ref(jnp.asarray(u, jnp.float32), bound)
        tol = 1e-2 if use_bf16 else 1e-6
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want), rtol=tol, atol=tol)
        assert out.dtype == u.dtype

    @settings(max_examples=30, deadline=None)
    @given(v=st.integers(1, 300), n=st.integers(1, 8),
           block_v=st.sampled_from([8, 64, 256]),
           seed=st.integers(0, 2**31 - 1))
    def test_batched_affine_property_matches_ref(v, n, block_v, seed):
        """ops.batched_affine == ref einsum for arbitrary (V, n, n)."""
        rng = np.random.default_rng(seed)
        p = jnp.asarray(rng.standard_normal((v, n, n)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((v, n)), jnp.float32)
        out = ops.batched_affine(p, x, block_v=block_v)
        want = ref.batched_affine_ref(p, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_tv_prox_property_matches_ref():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_batched_affine_property_matches_ref():
        pass
