"""The custom-VJP norm gradient checks (round 5) and the pallas xent and
dropout kernels' cases and gates.

The xent kernel case needs the real chip (chip_smoke.py runs it there);
the norm gradient tests and the gates run everywhere. The decode tick's
kernels (ops/decode_step.py) are held by tests/test_serving.py and
tests/test_tpu_compile.py.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

needs_tpu = pytest.mark.needs_tpu      # skipped off-chip by conftest.py


# ---------------------------------------------------------------------------
# custom-VJP norms: gradients == autodiff of the plain formulation
# ---------------------------------------------------------------------------

def _ref_layernorm(x, s, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    m = jnp.mean(x32, -1, keepdims=True)
    v = jnp.var(x32, -1, keepdims=True)
    y = (x32 - m) / jnp.sqrt(v + eps) * s.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _ref_rmsnorm(x, s, eps=1e-5):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), -1, keepdims=True)
    return (x32 / jnp.sqrt(ms + eps) * s.astype(jnp.float32)).astype(x.dtype)


@pytest.fixture()
def _norm_inputs():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 64)) * 2 + 0.3
    s = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.5 + 1.0
    b = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
    return x, s, b


def test_layernorm_custom_vjp_gradients(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import layernorm

    x, s, b = _norm_inputs
    np.testing.assert_allclose(layernorm(x, s, b), _ref_layernorm(x, s, b),
                               rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(layernorm(*a))), (0, 1, 2))(
        x, s, b)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(_ref_layernorm(*a))), (0, 1, 2))(
        x, s, b)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


def test_layernorm_custom_vjp_gradients_no_bias(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import layernorm

    x, s, _ = _norm_inputs
    g1 = jax.grad(lambda x, s: jnp.sum(jnp.sin(layernorm(x, s, None))),
                  (0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(jnp.sin(_ref_layernorm(x, s, None))),
                  (0, 1))(x, s)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


def test_rmsnorm_custom_vjp_gradients(_norm_inputs):
    from building_llm_from_scratch_tpu.ops.norms import rmsnorm

    x, s, _ = _norm_inputs
    np.testing.assert_allclose(rmsnorm(x, s), _ref_rmsnorm(x, s),
                               rtol=1e-6, atol=1e-6)
    g1 = jax.grad(lambda x, s: jnp.sum(jnp.sin(rmsnorm(x, s))), (0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(jnp.sin(_ref_rmsnorm(x, s))),
                  (0, 1))(x, s)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


@needs_tpu
@pytest.mark.parametrize("N,D,V", [(1024, 768, 50257), (256, 128, 999)])
def test_pallas_xent_fwd_matches_xla(N, D, V):
    """ops/xent_fwd_pallas.py (opt-in BLLM_XENT_PALLAS=1): nll and lse
    match the XLA online-logsumexp forward exactly."""
    from building_llm_from_scratch_tpu.ops.softmax_xent import (
        _xent_fwd_xla,
    )
    from building_llm_from_scratch_tpu.ops.xent_fwd_pallas import xent_fwd

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (N, D), jnp.bfloat16)
    w = jax.random.normal(ks[1], (D, V), jnp.bfloat16) * 0.02
    t = jax.random.randint(ks[2], (N,), 0, V)
    nll, lse = jax.jit(xent_fwd)(x, w, t)
    nll_ref, lse_ref = _xent_fwd_xla(x, w, t, 51200)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nll), np.asarray(nll_ref),
                               rtol=1e-4, atol=2e-4)


def test_pallas_xent_supports_shape_gates():
    from building_llm_from_scratch_tpu.ops.xent_fwd_pallas import (
        supports_shape,
    )

    assert supports_shape(8192, 768, 50257)
    assert not supports_shape(100, 768, 50257)     # row misalignment
    assert not supports_shape(65536, 4096, 128256)  # VMEM blowout


def test_fused_dropout_degenerate_rows_fall_back():
    """ADVICE r4 low #3: prime leading dims (best row block < 8) must not
    take the pallas path."""
    from building_llm_from_scratch_tpu.ops.fused_dropout import (
        supports_shape,
    )

    assert supports_shape((8, 1024, 768))
    assert not supports_shape((997, 128))     # prime rows -> r degenerates
    assert not supports_shape((1, 3, 128))    # tiny fold
    assert not supports_shape((8, 100))       # lane misalignment