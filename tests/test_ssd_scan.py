"""``F.ssd_scan`` (the chunked Mamba-2 recurrence) against the recurrence
itself, position by position, and ``F.causal_conv1d`` against a loop over
taps: forward and the gradient of every operand, in float32, at one chunk,
several chunks, a chunk count that is no power of two, a length that is no
whole number of chunks, and heads over groups as published (16 a group)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.registry import raw

ssd_scan = raw("ssd_scan")
causal_conv1d = raw("causal_conv1d")


def recurrence(x, dt, A, B, C, D):
    """``S[t] = exp(dt A) S[t-1] + dt x (outer) B; y = S C + D x``, one
    position a step, one row."""
    nh, g = x.shape[1], B.shape[1]
    B, C = (jnp.repeat(a, nh // g, axis=1) for a in (B, C))   # by head

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * A)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + D[:, None] * x_t

    s0 = jnp.zeros((nh, x.shape[2], B.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (x, dt, B, C))[1]


def operands(seed, batch, t, nh, p, g, n):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    x, B, C = f(batch, t, nh, p), f(batch, t, g, n), f(batch, t, g, n)
    dt = jax.nn.softplus(f(batch, t, nh) - 2.0)
    A = -jnp.exp(jnp.asarray(r.uniform(0.0, 1.5, nh), jnp.float32))
    return x, dt, A, B, C, f(nh)


# (T, chunk, heads, groups): one chunk; four; three (no power of two); a
# length that is no whole number of chunks; 16 heads a group, as published
CASES = [(16, 16, 4, 2), (64, 16, 4, 2), (48, 16, 4, 1), (40, 16, 2, 2),
         (32, 8, 32, 2)]


@pytest.mark.parametrize("t,chunk,nh,g", CASES)
def test_chunked_scan_is_the_recurrence(t, chunk, nh, g):
    """float32 throughout, so the two differ by the order of their sums
    alone: 2e-5 of the output's scale (about 3) covers exp and the longer
    sums of the chunked form."""
    ops = operands(t + nh, 2, t, nh, 8, g, 16)
    x, dt, A, B, C, D = ops
    got = ssd_scan(*ops, chunk=chunk)
    want = jax.vmap(lambda x, dt, B, C: recurrence(x, dt, A, B, C, D))(
        x, dt, B, C)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=6e-5)


@pytest.mark.parametrize("t,chunk,nh,g", CASES)
def test_chunked_scan_gradients_of_every_operand(t, chunk, nh, g):
    """The chunked form differentiated against the recurrence
    differentiated, under one random cotangent; each operand's gradient to
    1e-4 of that gradient's largest entry (float32 sums in another
    order)."""
    ops = operands(t + nh + 1, 2, t, nh, 8, g, 16)
    co = jnp.asarray(np.random.default_rng(5).standard_normal(ops[0].shape),
                     jnp.float32)

    def chunked(*o):
        return jnp.sum(ssd_scan(*o, chunk=chunk) * co)

    def plain(x, dt, A, B, C, D):
        return jnp.sum(jax.vmap(lambda x, dt, B, C: recurrence(
            x, dt, A, B, C, D))(x, dt, B, C) * co)

    got = jax.grad(chunked, argnums=tuple(range(6)))(*ops)
    want = jax.grad(plain, argnums=tuple(range(6)))(*ops)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=name)


def test_a_decay_that_would_overflow_above_the_diagonal_stays_finite():
    """Large steps: ``alpha_i - alpha_j`` above the diagonal passes 88 and
    ``exp`` of it is inf in float32; it is masked before the exp, so
    forward and gradients stay finite."""
    x, dt, A, B, C, D = operands(3, 1, 32, 2, 4, 1, 8)
    dt, A = dt * 0 + 8.0, A * 0 - 4.0
    val, grads = jax.value_and_grad(
        lambda *o: jnp.sum(ssd_scan(*o, chunk=16)), argnums=(0, 1, 2))(
        x, dt, A, B, C, D)
    assert np.isfinite(val) and all(np.all(np.isfinite(g)) for g in grads)


def test_bfloat16_operands_keep_float32_sums():
    """In bfloat16 the output is bfloat16 and stays within bfloat16's
    rounding of the float32 result (operands rounded to 8 bits of mantissa,
    sums in float32: 3e-2 of the output's scale)."""
    ops = operands(11, 1, 64, 4, 8, 2, 16)
    x, dt, A, B, C, D = ops
    low = ssd_scan(x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
                   C.astype(jnp.bfloat16), D, chunk=16)
    assert low.dtype == jnp.bfloat16
    want = ssd_scan(*ops, chunk=16)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - want))) \
        < 3e-2 * scale


@pytest.mark.parametrize("taps", [1, 2, 4])
def test_causal_conv_is_a_sum_of_shifted_products(taps):
    r = np.random.default_rng(taps)
    x = r.standard_normal((2, 9, 6)).astype(np.float32)
    w = r.standard_normal((6, taps)).astype(np.float32)
    b = r.standard_normal(6).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(x.shape[1]):
        for k in range(taps):
            src = t - (taps - 1) + k
            if src >= 0:
                want[:, t] += w[:, k] * x[:, src]
    np.testing.assert_allclose(causal_conv1d(x, w, b), want + b, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(causal_conv1d(x, w), want, rtol=1e-5,
                               atol=1e-6)
    # causal: a later position changes no earlier output
    x2 = x.copy()
    x2[:, 5:] += 1.0
    np.testing.assert_array_equal(
        np.asarray(causal_conv1d(x2, w, b))[:, :5],
        np.asarray(causal_conv1d(x, w, b))[:, :5])
