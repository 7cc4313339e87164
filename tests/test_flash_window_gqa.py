"""Sliding windows and grouped KV heads in the flash kernels
(``ops/pallas/attention_kernel.py``), in interpret mode on the CPU:

- forward and all three gradients against dense masked attention in
  float32 (an explicit ``0 <= t - j < window`` mask, K and V expanded to
  the q heads), for windows smaller than, equal to and larger than a
  block, a window as long as the sequence (must equal plain causal), and
  groups of 1, 6 and 9;
- the block ranges a window visits;
- the dispatcher: the XLA composition computes the same function of
  ``window`` and of the head counts, what it refuses, and that a call
  without a window and with equal heads is the call it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import attention_kernel as ak

SEQ, BLOCK, HEAD = 256, 64, 32


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _dense(q, k, v, window):
    """[B, T, N, H] causal attention in float32 with an explicit window
    mask, K and V expanded to the q heads."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("btnh,bsnh->bnts", q, k) / np.sqrt(q.shape[-1])
    back = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]
    seen = back >= 0
    if window is not None:
        seen &= back < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bnts,bsnh->btnh", p, v)


def _qkv(group, kv_heads, dtype=jnp.float32, batch=2, seq=SEQ):
    q = _rand((batch, seq, kv_heads * group, HEAD), 1, dtype)
    k = _rand((batch, seq, kv_heads, HEAD), 2, dtype)
    v = _rand((batch, seq, kv_heads, HEAD), 3, dtype)
    do = _rand((batch, seq, kv_heads * group, HEAD), 4, dtype)
    return q, k, v, do


@pytest.fixture
def small_blocks(monkeypatch):
    """64 x 64 blocks, so that a 256-token sequence has four."""
    monkeypatch.setattr(ak, "_blocks", lambda seq_q, seq_k: (BLOCK, BLOCK))


def _out_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


# smaller than a block; one block; larger and on no block's edge; two
# blocks; the whole sequence; none
WINDOWS = [16, BLOCK, 100, 2 * BLOCK, SEQ, None]


@pytest.mark.parametrize("group,kv_heads", [(1, 2), (6, 1), (9, 1), (6, 2)])
@pytest.mark.parametrize("window", WINDOWS)
def test_kernels_against_dense_masked_attention(small_blocks, window, group,
                                                kv_heads):
    q, k, v, do = _qkv(group, kv_heads)
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=True, interpret=True, window=window),
        q, k, v, do)
    want = _out_and_grads(lambda q, k, v: _dense(q, k, v, window),
                          q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


# (q/k width, v width, q heads over kv heads, window, causal, dtype): one
# width; 192 | 128; grouped 2 and 6; a window narrower than, equal to and
# wider than a block; non-causal (every k block visits every q block);
# the chip's dtype
FUSED_BACKWARD_CASES = {
    "one-width": (32, 32, (2, 2), None, True, jnp.float32),
    "mla192|128": (192, 128, (2, 2), None, True, jnp.float32),
    "grouped2": (32, 32, (4, 2), None, True, jnp.float32),
    "grouped6": (32, 32, (6, 1), None, True, jnp.float32),
    "window16": (32, 32, (2, 2), 16, True, jnp.float32),
    "window64-grouped2": (32, 32, (4, 2), BLOCK, True, jnp.float32),
    "window100-grouped6": (32, 32, (6, 1), 100, True, jnp.float32),
    "non-causal": (32, 32, (2, 2), None, False, jnp.float32),
    "non-causal-grouped2": (32, 32, (4, 2), None, False, jnp.float32),
    "bf16-mla192|128": (192, 128, (2, 2), None, True, jnp.bfloat16),
    "bf16-window100-grouped6": (32, 32, (6, 1), 100, True, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(FUSED_BACKWARD_CASES))
def test_the_one_backward_kernel_against_the_xla_composition(small_blocks,
                                                             case):
    """The backward is ONE kernel: S, the mask, P and dP formed once a block
    pair, dQ summed in VMEM over the q head's four k blocks, dK and dV over
    the group.  All three gradients against ``_xla_attention``'s."""
    head, head_v, (n, nkv), window, causal, dtype = FUSED_BACKWARD_CASES[case]
    q = _rand((2, SEQ, n, head), 11, dtype)
    k = _rand((2, SEQ, nkv, head), 12, dtype)
    v = _rand((2, SEQ, nkv, head_v), 13, dtype)
    do = _rand((2, SEQ, n, head_v), 14, dtype)
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=causal, interpret=True, window=window),
        q, k, v, do)
    f32 = lambda x: x.astype(jnp.float32)                   # noqa: E731
    want = _out_and_grads(
        lambda q, k, v: pk._xla_attention(f32(q), f32(k), f32(v),
                                          is_causal=causal, window=window),
        q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == w.shape and g.dtype == dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=5e-5, atol=5e-5,
                                       err_msg=name)
        else:           # two bf16 steps of the tensor's largest value
            assert np.abs(g - w).max() <= 2 * 2.0 ** -8 * np.abs(w).max(), \
                name


@pytest.mark.parametrize("group", [1, 6])
def test_a_window_as_long_as_the_sequence_is_plain_causal(small_blocks,
                                                          group):
    """Bit for bit: the public call hands it on as no window at all, and
    the kernels given the window itself (every block visited, every mask
    the causal one) compute the same numbers."""
    q, k, v, do = _qkv(group, 2)
    run = lambda w: _out_and_grads(                 # noqa: E731
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=True, interpret=True, window=w), q, k, v, do)
    plain = run(None)
    for w in (SEQ, SEQ + 7):
        for a, b in zip(run(w), plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the kernels themselves, below the public call's short cut
    scale = 1.0 / np.sqrt(HEAD)
    dims = (q.shape[0], q.shape[2], k.shape[2])
    qk, kk, vk, dok = (ak._to_kernel(x, group) for x in (q, k, v, do))
    for w in (None, SEQ):
        out, lse = ak._flash_fwd(qk, kk, vk, dims, True, scale, BLOCK, BLOCK,
                                 True, w)
        grads = ak._flash_bwd(qk, kk, vk, out, lse, dok, dims, True, scale,
                              BLOCK, BLOCK, True, w)
        if w is None:
            first = (out,) + tuple(grads)
        else:
            for a, b in zip((out,) + tuple(grads), first):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_window_and_groups_stay_within_two_bf16_steps(small_blocks):
    """The chip's dtype: each compared tensor within two bf16 steps of its
    largest value (``tests/test_pallas_kernels.py`` says why two)."""
    q, k, v, do = _qkv(9, 1, jnp.bfloat16)
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=True, interpret=True, window=100), q, k, v, do)
    want = _out_and_grads(lambda q, k, v: _dense(q, k, v, 100), q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16, name
        w = np.asarray(w, np.float32)
        err = np.abs(np.asarray(g, np.float32) - w).max()
        assert err <= 2 * 2.0 ** -8 * np.abs(w).max(), (name, err)


@pytest.mark.parametrize("window,blocks", [(512, 512), (512, 256), (100, 64),
                                           (1, 128), (2048, 256)])
def test_block_ranges_hold_every_visible_pair_and_little_else(window, blocks):
    """``_key_blocks`` / ``_query_blocks`` against the mask itself: every
    (q block, k block) with a visible pair is visited, both ways round,
    and a q block visits at most the blocks a window can reach."""
    seq = 2048
    n = seq // blocks
    back = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    seen = (back >= 0) & (back < window)
    need = seen.reshape(n, blocks, n, blocks).any(axis=(1, 3))
    for i in range(n):
        first, end = (int(x) for x in ak._key_blocks(
            i, blocks, blocks, seq, True, window))
        assert need[i, first:end].all() and not need[i, :first].any() \
            and not need[i, end:].any(), i
        assert end - first <= (window - 1 + blocks - 1) // blocks + 1
        first, end = (int(x) for x in ak._query_blocks(
            i, blocks, blocks, n, True, window))
        assert need[first:end, i].all() and not need[:first, i].any() \
            and not need[end:, i].any(), i


def test_window_calls_are_named_apart_and_plain_calls_as_ever():
    """A trace tells window calls from full ones by the kernels' names; a
    call with neither window nor grouped heads has the names, grids and
    operands it had."""
    x = jax.ShapeDtypeStruct((2, 256, 4, 32), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 256, 2, 32), jnp.float32)

    def calls(k, window):
        from paddle_tpu.framework.analysis import walk_jaxprs

        def f(q, k, v):
            loss = lambda *a: jnp.sum(ak.flash_attention_pallas(  # noqa: E731
                *a, is_causal=True, window=window))
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        jaxpr = jax.make_jaxpr(f)(x, k, k)
        return {e.params["name"]: e for _, sub in walk_jaxprs(jaxpr)
                for e in sub.eqns if e.primitive.name == "pallas_call"}

    plain = calls(x, None)
    assert set(plain) == {"flash_attention_fwd", "flash_attention_bwd_dq_dkv"}
    assert set(calls(x, 64)) == {"flash_window64_attention_fwd",
                                 "flash_window64_attention_bwd_dq_dkv"}
    grouped = calls(kv, None)
    assert set(grouped) == set(plain)
    # the backward's grid: (kv head, q head of its group, k block); the q
    # head is OUTSIDE the k blocks, so its dQ stays in VMEM while they run
    grid = lambda e: tuple(e.params["grid_mapping"].grid)   # noqa: E731
    bwd = "flash_attention_bwd_dq_dkv"
    assert grid(plain[bwd])[:2] == (2 * 4, 1)
    assert grid(grouped[bwd])[:2] == (2 * 2, 2)
    # scratch: float32 dQ of the q head; grouped, whole-sequence dK and dV
    assert plain[bwd].params["grid_mapping"].num_scratch_operands == 1
    assert grouped[bwd].params["grid_mapping"].num_scratch_operands == 3


@pytest.mark.parametrize("group", [1, 3])
@pytest.mark.parametrize("window", [None, 5, 40])
def test_xla_composition_computes_the_same_function(window, group):
    q, k, v, do = _qkv(group, 2, seq=48)
    got = _out_and_grads(
        lambda q, k, v: pk.flash_attention(q, k, v, is_causal=True,
                                           window=window), q, k, v, do)
    want = _out_and_grads(lambda q, k, v: _dense(q, k, v, window),
                          q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_sdpa_passes_window_and_grouped_heads_on():
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F

    q, k, v, _ = _qkv(3, 2, seq=48)
    got = F.scaled_dot_product_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
        paddle.to_tensor(np.asarray(v)), is_causal=True, window=7)
    np.testing.assert_allclose(np.asarray(got._data),
                               np.asarray(_dense(q, k, v, 7)), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("q_heads,kv_heads,window,causal,ok", [
    (48, 8, None, True, True), (72, 8, 512, True, True),
    (32, 32, None, False, True), (72, 7, 512, True, False),
    (72, 8, 512, False, False), (72, 8, 0, True, False)])
def test_supports_says_what_is_true(q_heads, kv_heads, window, causal, ok):
    assert ak.supports(8192, 8192, 128, 128, q_heads, kv_heads, window,
                       causal) is ok
    if not ok:
        q = jax.ShapeDtypeStruct((1, 256, q_heads, 32), jnp.float32)
        k = jax.ShapeDtypeStruct((1, 256, kv_heads, 32), jnp.float32)
        with pytest.raises(ValueError, match="kv heads must divide"):
            jax.eval_shape(lambda q, k: ak.flash_attention_pallas(
                q, k, k, is_causal=causal, window=window), q, k)


def test_dispatch_takes_the_kernels_for_window_and_groups(monkeypatch):
    """On the kernel path (``_use_pallas`` patched true, lowered for the
    TPU) the cell's two calls reach the kernels under their own names and
    nothing falls back; K and V arrive at their own head count."""
    import warnings

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    q72 = jax.ShapeDtypeStruct((1, 8192, 72, 128), jnp.bfloat16)
    q48 = jax.ShapeDtypeStruct((1, 8192, 48, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)

    def lowered(q, window):
        def f(q, k, v):
            loss = lambda *a: jnp.sum(pk.flash_attention(   # noqa: E731
                *a, is_causal=True, window=window).astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        with warnings.catch_warnings():
            warnings.simplefilter("error", pk.KernelFallbackWarning)
            return jax.jit(f).trace(q, kv, kv).lower(
                lowering_platforms=("tpu",)).as_text()

    text = lowered(q72, 512)
    for kernel in ("fwd", "bwd_dq_dkv"):
        assert f'kernel_name = "flash_window512_attention_{kernel}"' in text
    assert "dot_general" not in text and "8192x8192" not in text
    assert "1x8192x72x128" in text and "72x8192x8192" not in text
    text = lowered(q48, None)
    for kernel in ("fwd", "bwd_dq_dkv"):
        assert f'kernel_name = "flash_attention_{kernel}"' in text
    assert "dot_general" not in text


def test_dispatch_refusals_say_why(monkeypatch):
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 2048, 6, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2048, 4, 64), jnp.float32)
    with pytest.warns(pk.KernelFallbackWarning, match="supports"):
        with pytest.raises(Exception):     # the composition cannot group 6/4
            jax.eval_shape(lambda q, k: pk.flash_attention(
                q, k, k, is_causal=True), q, k)
    k = jax.ShapeDtypeStruct((1, 2048, 3, 64), jnp.float32)
    with pytest.warns(pk.KernelFallbackWarning,
                      match="a causal sliding window it takes as window="):
        jax.eval_shape(lambda q, k: pk.flash_attention(
            q, k, k, is_causal=True, scale=0.5), q, k)
