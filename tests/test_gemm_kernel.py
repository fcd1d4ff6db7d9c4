"""Pallas GEMM kernel vs pure-jnp oracle: shape/dtype/epilogue sweeps +
hypothesis property tests (task-required per-kernel validation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, strategies as st

from repro.core.tile_config import TileConfig
from repro.kernels import ops
from repro.kernels.gemm import gemm_pallas
from repro.kernels.ref import gemm_ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


SHAPES = [
    (8, 16, 8), (32, 32, 32), (33, 65, 17), (64, 128, 96),
    (100, 100, 100), (1, 256, 7), (128, 64, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_gemm_shape_dtype_sweep(m, k, n, dtype):
    a, b = _rand((m, k), dtype, 1), _rand((k, n), dtype, 2)
    cfg = TileConfig(16, 32, 16)
    out = ops.gemm(a, b, config=cfg, backend=ops.BACKEND_PALLAS_INTERPRET)
    ref = gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("activation", [None, "relu", "gelu", "silu", "tanh"])
def test_gemm_epilogues(activation):
    m, k, n = 48, 64, 40
    a, b = _rand((m, k), jnp.float32, 3), _rand((k, n), jnp.float32, 4)
    bias = _rand((n,), jnp.float32, 5)
    cfg = TileConfig(16, 16, 16)
    out = ops.gemm(a, b, config=cfg, backend=ops.BACKEND_PALLAS_INTERPRET,
                   bias=bias, activation=activation)
    ref = gemm_ref(a, b, bias=bias, activation=activation)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_gemm_alpha_beta_full_form():
    """Paper Eq. 1: C = alpha*A@B + beta*C."""
    m, k, n = 32, 48, 32
    a, b = _rand((m, k), jnp.float32, 6), _rand((k, n), jnp.float32, 7)
    c = _rand((m, n), jnp.float32, 8)
    out = gemm_pallas(a, b, c, bm=16, bk=16, bn=16, alpha=1.7, beta=0.3,
                      interpret=True)
    ref = gemm_ref(a, b, c, alpha=1.7, beta=0.3)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_gemm_out_dtype_override():
    a, b = _rand((32, 32), jnp.bfloat16, 9), _rand((32, 32), jnp.bfloat16, 10)
    out = ops.gemm(a, b, config=TileConfig(16, 16, 16),
                   backend=ops.BACKEND_PALLAS_INTERPRET, out_dtype=jnp.float32)
    assert out.dtype == jnp.float32


def test_batched_gemm():
    a = _rand((3, 2, 16, 24), jnp.float32, 11)
    b = _rand((3, 2, 24, 8), jnp.float32, 12)
    out = ops.batched_gemm(a, b, config=TileConfig(8, 8, 8),
                           backend=ops.BACKEND_PALLAS_INTERPRET)
    ref = jnp.einsum("...ij,...jk->...ik", a, b)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_all_backends_agree():
    a, b = _rand((40, 56, ), jnp.float32, 13).reshape(40, 56), _rand((56, 24), jnp.float32, 14)
    outs = {}
    for backend in (ops.BACKEND_REF, ops.BACKEND_XLA, ops.BACKEND_PALLAS_INTERPRET):
        outs[backend] = ops.gemm(a, b, config=TileConfig(8, 8, 8), backend=backend)
    for backend, out in outs.items():
        np.testing.assert_allclose(out, outs[ops.BACKEND_REF], rtol=1e-5,
                                   atol=1e-5, err_msg=backend)


@pytest.mark.parametrize("activation,bias", [(None, False), ("silu", True),
                                             ("gelu", False)])
def test_pallas_gemm_gradients_match_xla(activation, bias):
    """The Pallas GEMM's custom VJP (two GEMMs through the same kernel,
    plus a recomputed pre-activation) gives the XLA path's gradients, at
    shapes that need padding to the tile grid."""
    a, b = _rand((40, 70), jnp.float32, 15), _rand((70, 50), jnp.float32, 16)
    bias_v = _rand((50,), jnp.float32, 17) if bias else None

    def loss(backend):
        def f(a, b, bias_v):
            out = ops.gemm(a, b, config=TileConfig(16, 32, 16),
                           backend=backend, bias=bias_v,
                           activation=activation)
            return (out * out).sum()
        return f

    argnums = (0, 1, 2) if bias else (0, 1)
    got = jax.grad(loss(ops.BACKEND_PALLAS_INTERPRET), argnums)(a, b, bias_v)
    want = jax.grad(loss(ops.BACKEND_XLA), argnums)(a, b, bias_v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis)
# ---------------------------------------------------------------------------

small = st.integers(min_value=1, max_value=24)


@settings(max_examples=15, deadline=None)
@given(m=small, k=small, n=small, seed=st.integers(0, 2**16))
def test_property_matches_oracle(m, k, n, seed):
    a, b = _rand((m, k), jnp.float32, seed), _rand((k, n), jnp.float32, seed + 1)
    out = ops.gemm(a, b, config=TileConfig(8, 8, 8),
                   backend=ops.BACKEND_PALLAS_INTERPRET)
    np.testing.assert_allclose(out, gemm_ref(a, b), rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(m=small, k=small, seed=st.integers(0, 2**16))
def test_property_identity(m, k, seed):
    """A @ I == A (exactly representable)."""
    a = _rand((m, k), jnp.float32, seed)
    eye = jnp.eye(k, dtype=jnp.float32)
    out = ops.gemm(a, eye, config=TileConfig(8, 8, 8),
                   backend=ops.BACKEND_PALLAS_INTERPRET)
    np.testing.assert_allclose(out, a, rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(m=small, k=small, n=small, seed=st.integers(0, 2**16))
def test_property_linearity(m, k, n, seed):
    """(A1 + A2) @ B == A1 @ B + A2 @ B within f32 tolerance."""
    a1 = _rand((m, k), jnp.float32, seed)
    a2 = _rand((m, k), jnp.float32, seed + 7)
    b = _rand((k, n), jnp.float32, seed + 13)
    cfg = TileConfig(8, 8, 8)
    lhs = ops.gemm(a1 + a2, b, config=cfg, backend=ops.BACKEND_PALLAS_INTERPRET)
    rhs = ops.gemm(a1, b, config=cfg, backend=ops.BACKEND_PALLAS_INTERPRET) \
        + ops.gemm(a2, b, config=cfg, backend=ops.BACKEND_PALLAS_INTERPRET)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)
