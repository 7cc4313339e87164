"""The block-diffusion mask in the flash kernels
(``ops/pallas/attention_kernel.py``, ``block_diffusion=B``), in interpret
mode on the CPU.  The row is ``[noised ; clean]``, two copies of ``L``
positions; with ``blk(i) = (i mod L) // B`` query ``i`` sees key ``j`` iff

    i <  L, j <  L:  blk(i) == blk(j)       i <  L, j >= L:  blk(j) <  blk(i)
    i >= L, j <  L:  never                  i >= L, j >= L:  blk(j) <= blk(i)

- the mask (the dispatcher's ``block_diffusion_mask`` and the kernels' tile
  form) against a brute-force ``[2 L, 2 L]`` table built from those four
  cases;
- forward and all three gradients against dense masked attention in float32
  (K and V expanded to the q heads), at ``B`` 4 and other ``B``, with ``L``
  a multiple of the key block and NOT one (a key block then straddles the
  two halves), groups of 1, 2 and 6;
- the two ranges of blocks a q block and a k block visit: every visible pair
  inside, no block twice, and the count the counters report;
- the dispatcher: the XLA composition computes the same function, what is
  refused and why, the kernels' names.

Tolerance 2e-5: float32 online softmax against float32 dense softmax
(``tests/test_flash_window_gqa.py`` holds the other masks to the same).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import attention_kernel as ak

HEAD = 32


def table(half, block):
    """The brute-force ``[2 L, 2 L]`` table, pair by pair."""
    seen = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            bi, bj = (i % half) // block, (j % half) // block
            if i < half and j < half:
                seen[i, j] = bi == bj
            elif i < half:
                seen[i, j] = bj < bi
            elif j >= half:
                seen[i, j] = bj <= bi
    return seen


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _dense(q, k, v, block):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("btnh,bsnh->bnts", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.asarray(table(q.shape[1] // 2, block))
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bnts,bsnh->btnh", p, v)


def _out_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


@pytest.fixture
def blocks(monkeypatch):
    """``blocks(bq, bk)`` forces the kernels' block sizes."""
    def force(block_q, block_k):
        monkeypatch.setattr(ak, "_blocks",
                            lambda seq_q, seq_k: (block_q, block_k))
    return force


# (q block, k block, L, B, q heads, kv heads): L a multiple of both blocks;
# of the q block alone (a key block straddles the halves); the other way
# round (k blocks smaller); B the whole q block; B 2
CASES = {
    "aligned-b4": (64, 64, 128, 4, 2, 2),
    "straddling-b4-grouped2": (64, 128, 192, 4, 4, 2),
    "straddling-b16-grouped6": (64, 128, 192, 16, 6, 1),
    "straddling-b8": (32, 64, 96, 8, 2, 1),
    "small-k-blocks-b2": (128, 64, 128, 2, 2, 2),
    "block-is-the-q-block": (32, 32, 64, 32, 2, 1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_mask_is_the_four_cases(case):
    _, _, half, block, _, _ = CASES[case]
    want = table(half, block)
    np.testing.assert_array_equal(
        np.asarray(pk.block_diffusion_mask(2 * half, block)), want)
    assert want.sum() == ak.blockdiff_pairs_needed(half, block) \
        == half * (half + block)
    assert want.any(axis=1).all()       # every row sees its own block
    # the kernels' tile form, forward (rows on axis 0) and backward (axis 1),
    # on a tile that straddles both halves' border
    ones = jnp.ones((2 * half, 2 * half), jnp.float32)
    fwd = ak._mask_block_diffusion(ones, 0, 0, 0, half, block)
    bwd = ak._mask_block_diffusion(ones, 0, 0, 1, half, block)
    np.testing.assert_array_equal(np.asarray(fwd) == 1.0, want)
    np.testing.assert_array_equal(np.asarray(bwd) == 1.0, want.T)
    off = ak._mask_block_diffusion(ones[:half, :half // 2], half // 2,
                                   3 * half // 4, 0, half, block)
    np.testing.assert_array_equal(
        np.asarray(off) == 1.0,
        want[half // 2:3 * half // 2, 3 * half // 4:5 * half // 4])


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_against_dense_masked_attention(blocks, case):
    block_q, block_k, half, block, n, nkv = CASES[case]
    blocks(block_q, block_k)
    shape = lambda heads: (2, 2 * half, heads, HEAD)        # noqa: E731
    q, k, v, do = (_rand(shape(n), 1), _rand(shape(nkv), 2),
                   _rand(shape(nkv), 3), _rand(shape(n), 4))
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, interpret=True, block_diffusion=block), q, k, v, do)
    want = _out_and_grads(lambda q, k, v: _dense(q, k, v, block),
                          q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_bf16_grouped_in_place_stays_within_two_bf16_steps(blocks):
    """The cell's layout at a small size: 128-wide heads, grouped, so all
    eight operands cross in place; bfloat16."""
    blocks(64, 64)
    q, k, v, do = (_rand((1, 256, 4, 128), 1, jnp.bfloat16),
                   _rand((1, 256, 2, 128), 2, jnp.bfloat16),
                   _rand((1, 256, 2, 128), 3, jnp.bfloat16),
                   _rand((1, 256, 4, 128), 4, jnp.bfloat16))
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, interpret=True, block_diffusion=4), q, k, v, do)
    assert pk.flash_layout_log()[-1] == {
        "kernel": "flash_blockdiff4_attention",
        "shapes": "q(1, 256, 4, 128) k(1, 256, 2, 128) v(1, 256, 2, 128)",
        "in_place": ("q", "k", "dq", "dk", "v", "o", "do", "dv"),
        "copied": {}}
    want = _out_and_grads(lambda q, k, v: _dense(q, k, v, 4), q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16, name
        scale = float(np.abs(np.asarray(w)).max())
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)), np.asarray(w), rtol=0,
            atol=2 * 2 ** -8 * scale, err_msg=name)


@pytest.mark.parametrize("case", list(CASES))
def test_block_ranges_hold_every_visible_pair_and_no_block_twice(case):
    block_q, block_k, half, block, _, _ = CASES[case]
    seen = table(half, block)
    num_qb, num_kb = 2 * half // block_q, 2 * half // block_k
    tiles = seen.reshape(num_qb, block_q, num_kb, block_k).any(axis=(1, 3))
    visited = np.zeros_like(tiles)
    for qi in range(num_qb):
        (a0, a1), (b0, b1) = (
            (int(lo), int(hi)) for lo, hi in ak._blockdiff_key_blocks(
                qi, block_q, block_k, half, block))
        assert 0 <= a0 <= a1 <= b0 <= b1 <= num_kb, (qi, a0, a1, b0, b1)
        visited[qi, a0:a1] = visited[qi, b0:b1] = True
    assert not (tiles & ~visited).any()             # nothing visible missed
    # and the k blocks' ranges are the transpose, block for block
    transposed = np.zeros_like(tiles)
    for ki in range(num_kb):
        (n0, n1), (m0, m1) = (
            (int(lo), int(hi)) for lo, hi in ak._blockdiff_query_blocks(
                ki, block_q, block_k, num_qb, half, block))
        assert 0 <= n0 <= n1 <= m0 <= m1 <= num_qb, (ki, n0, n1, m0, m1)
        transposed[n0:n1, ki] = transposed[m0:m1, ki] = True
    assert not (tiles & ~transposed).any()
    # little else: a visited tile holds a visible pair, or is the one
    # neighbour a range's rounding takes in
    assert visited.sum() <= tiles.sum() + num_qb
    assert transposed.sum() <= tiles.sum() + num_kb


def test_the_pairs_the_grid_scores_at_the_cells_shape():
    """512 x 512 blocks over 2 x 8192 positions in blocks of 4: a noised q
    block visits its own diagonal block and the clean blocks up to its own,
    a clean one the clean blocks up to its own: 288 tiles for 67.1M pairs."""
    assert ak._blocks(16384, 16384) == (512, 512)
    needed = ak.blockdiff_pairs_needed(8192, 4)
    scored = ak.blockdiff_pairs_scored(8192, 4)
    assert needed == 8192 * 8196 == 67141632
    assert scored == (16 + 2 * 136) * 512 * 512 == 75497472
    assert scored / needed < 1.13 < 1.5
    # the dense square is 4.0 times the mask, causal over 2 L 2.0
    assert 16384 ** 2 / needed == pytest.approx(4.0, rel=1e-3)


def test_blockdiff_calls_are_named_apart():
    q = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(ak.flash_attention_pallas(
            *a, block_diffusion=4).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    names = {line.split('kernel_name = "')[1].split('"')[0]
             for line in text.splitlines() if "tpu_custom_call" in line}
    assert names == {"flash_blockdiff4_attention_fwd",
                     "flash_blockdiff4_attention_bwd_dq_dkv"}
    # no accepted pattern counts them, and the cost function's marks do
    assert not any("flash_attention" in n or "flash_window" in n
                   for n in names)
    assert sum("_fwd" in n for n in names) == 1
    assert sum("_bwd_dq" in n for n in names) == 1


@pytest.mark.parametrize("block,group", [(4, 1), (4, 2), (16, 4)])
def test_xla_composition_computes_the_same_function(block, group):
    q, k, v = (_rand((2, 64, 2 * group, HEAD), 5), _rand((2, 64, 2, HEAD), 6),
               _rand((2, 64, 2, HEAD), 7))
    got = pk._xla_attention(q, k, v, block_diffusion=block)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense(q, k, v, block)),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="its own mask"):
        pk._xla_attention(q, k, v, is_causal=True, block_diffusion=block)


def test_sdpa_passes_the_mask_on():
    from paddle_tpu.core.tensor import Tensor

    q, k, v = (_rand((1, 64, 4, HEAD), 8), _rand((1, 64, 2, HEAD), 9),
               _rand((1, 64, 2, HEAD), 10))
    got = F.scaled_dot_product_attention(Tensor(q), Tensor(k), Tensor(v),
                                         block_diffusion=8)._data
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense(q, k, v, 8)), rtol=2e-5,
                               atol=2e-5)


# (seq, q heads, kv heads, window, causal, B, ok)
@pytest.mark.parametrize("seq,q_heads,kv_heads,window,causal,block,ok", [
    (16384, 32, 4, None, False, 4, True),       # the cell's call
    (2048, 4, 4, None, False, 512, True),       # B the whole block
    (2048, 4, 4, None, False, 3, False),        # B does not divide a block
    (2048, 4, 4, None, False, 1024, False),     # B larger than a block
    (2048, 4, 4, None, True, 4, False),         # it is not a causal mask
    (2048, 4, 4, 512, True, 4, False),          # nor a window's
    (1536, 4, 4, None, False, 4, False),        # L 768: not whole q blocks
    (1024, 4, 3, None, False, 4, False),        # heads as ever
])
def test_supports_says_what_is_true(seq, q_heads, kv_heads, window, causal,
                                    block, ok):
    assert ak.supports(seq, seq, 128, 128, q_heads, kv_heads, window, causal,
                       block) is ok
    if not ok and q_heads % kv_heads == 0 and window is None:
        x = jax.ShapeDtypeStruct((1, seq, q_heads, 128), jnp.float32)
        with pytest.raises(ValueError, match="block-diffusion"):
            jax.eval_shape(lambda q: ak.flash_attention_pallas(
                q, q, q, is_causal=causal, block_diffusion=block), x)


def test_dispatch_takes_the_kernels_and_counts_their_pairs(monkeypatch):
    """On the TPU (patched) the call reaches the kernels with the mask and
    the pairs are the grid's; off it, and for a shape ``supports`` refuses,
    the composition with the mask, the dense square's pairs, aloud only on
    the TPU."""
    calls = []
    monkeypatch.setattr(
        ak, "flash_attention_pallas",
        lambda q, k, v, is_causal=False, **kw: calls.append(
            (is_causal, kw)) or q)
    q = jnp.zeros((1, 2048, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 2048, 2, 128), jnp.bfloat16)
    assert pk.blockdiff_pairs(2048, 128, 4, 2, 4) \
        == (2048 * 2048, 1024 * 1028)              # off the TPU: dense
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    pk.flash_attention(q, kv, kv, block_diffusion=4)
    assert calls == [(False, {"block_diffusion": 4})]
    assert pk.blockdiff_pairs(2048, 128, 4, 2, 4) \
        == (ak.blockdiff_pairs_scored(1024, 4), 1024 * 1028)
    # B 3 divides no block: the composition, with a word (the warning is
    # raised on a TPU backend only: none here)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = pk.flash_attention(q, kv, kv, block_diffusion=3)
    assert out.shape == q.shape and len(calls) == 1
    assert pk.blockdiff_pairs(2048, 128, 4, 2, 3)[0] == 2048 * 2048
    record = [r for r in pk.common.traced_calls
              if r["kernel"] == "flash_attention"][-1]
    assert record["path"] == "composition" \
        and "supports() refuses" in record["reason"]
    # shorter than FLASH_MIN_SEQ: XLA's fused attention by choice
    short = jnp.zeros((1, 512, 4, 128), jnp.bfloat16)
    pk.flash_attention(short, short, short, block_diffusion=4)
    assert pk.blockdiff_pairs(512, 128, 4, 4, 4)[0] == 512 * 512
    assert len(calls) == 1
