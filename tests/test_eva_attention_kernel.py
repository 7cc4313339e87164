"""The ``eva_attention_*`` kernels (``ops/pallas/eva_attention_kernel.py``)
in interpret mode against the dense XLA composition
(``ops.pallas._xla_eva_attention``): the output and all of dq, dk, dv, dkt,
dvt, over rows of one, two and five windows, windows of one and of several
blocks, and summary blocks that do and do not divide a window's summaries.
And what the grids score against what the mask needs, at the cell's shape
(computed, not run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import eva_attention_kernel as eva

HEADS, DIM = 2, 16
NAMES = ("q", "k", "v", "kt", "vt")

# (windows, window, chunk, (block_q, block_k, block_s)): the summaries of a
# window are window / chunk
CASES = {
    "one-window": (1, 32, 4, (32, 32, 8)),
    "two-windows-of-one-block": (2, 32, 4, (32, 32, 8)),
    "five-windows-of-one-block": (5, 32, 4, (32, 32, 8)),
    "two-windows-of-four-blocks": (2, 32, 4, (8, 8, 8)),
    "five-windows-q16-k8": (5, 32, 4, (16, 8, 8)),
    "five-windows-q8-k16": (5, 32, 4, (8, 16, 8)),
    # 8 summaries a window in blocks of 16: the last block is masked, and
    # 5 x 8 = 40 summaries are padded to 48
    "five-windows-summary-block-over-two-windows": (5, 32, 4, (16, 16, 16)),
    # 16 summaries a window in blocks of 8: two whole blocks a window
    "three-windows-two-summary-blocks-each": (3, 32, 2, (16, 16, 8)),
    "default-blocks": (3, 64, 4, None),
}


def _operands(nw, window, chunk, seed=0, batch=1):
    t = nw * window
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = {"q": t, "k": t, "v": t, "kt": t // chunk, "vt": t // chunk}
    return [jax.random.normal(key, (batch, shape[n], HEADS, DIM),
                              jnp.float32)
            for n, key in zip(NAMES, keys)]


def _loss(fn, weights):
    return lambda *a: jnp.sum(fn(*a) * weights)


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_xla_composition(case):
    """Float32 operands in interpret mode: the online softmax and the
    blockwise sums differ from the dense form by rounding alone."""
    nw, window, chunk, blocks = CASES[case]
    ops = _operands(nw, window, chunk)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def kernel(*a):
        return eva.eva_attention_pallas(*a, window=window, chunk=chunk,
                                        interpret=True, blocks=blocks)

    def dense(*a):
        return pk._xla_eva_attention(*a, window, chunk)

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(
            _loss(kernel, weights), argnums=range(5))(*ops)
        want, want_grads = jax.value_and_grad(
            _loss(dense, weights), argnums=range(5))(*ops)
        np.testing.assert_allclose(np.asarray(kernel(*ops)),
                                   np.asarray(dense(*ops)), rtol=2e-5,
                                   atol=2e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
    for name, g, w in zip(NAMES, got_grads, want_grads):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-4,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=f"d{name}")
    if nw == 1:
        # nobody reads a summary: their gradients are exact zeros
        assert not np.asarray(got_grads[3]).any()
        assert not np.asarray(got_grads[4]).any()
    else:
        # the last window's summaries are read by nobody, the first's are
        per_window = window // chunk
        for g in got_grads[3:]:
            g = np.asarray(g)
            assert not g[:, -per_window:].any()
            assert g[:, :per_window].any()


def test_bfloat16_operands_keep_float32_statistics():
    """The stated mix: bfloat16 operands, float32 scores and softmax.  The
    kernel and the composition round p to bfloat16 at different places
    (per block against once), so they agree to bfloat16's step, 2 ** -8,
    on values of order one."""
    nw, window, chunk, blocks = CASES["five-windows-q16-k8"]
    ops = [a.astype(jnp.bfloat16) for a in _operands(nw, window, chunk)]
    got = eva.eva_attention_pallas(*ops, window=window, chunk=chunk,
                                   interpret=True, blocks=blocks)
    want = pk._xla_eva_attention(*ops, window, chunk)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2 ** -6)


@pytest.mark.parametrize("fault", ["own-summaries", "sliding-window"])
def test_the_composition_is_not_its_neighbours(fault):
    """The dense composition itself against the two masks it must not be:
    the own window's summaries counted too, and a sliding window in the
    block one's place."""
    nw, window, chunk = 3, 32, 4
    q, k, v, kt, vt = _operands(nw, window, chunk, batch=1)
    t = nw * window
    pos = np.arange(t)
    if fault == "own-summaries":
        own = (pos[:, None] // window == pos[None, :] // window) \
            & (pos[None, :] <= pos[:, None])
        seen = (np.arange(t // chunk)[None, :] * chunk) // window \
            <= pos[:, None] // window
    else:
        back = pos[:, None] - pos[None, :]
        own = (back >= 0) & (back < window)
        seen = (np.arange(t // chunk)[None, :] * chunk) // window \
            < pos[:, None] // window
    scale = DIM ** -0.5
    s = jnp.concatenate(
        [jnp.where(own, jnp.einsum("btnh,bsnh->bnts", q, k) * scale,
                   -jnp.inf),
         jnp.where(seen, jnp.einsum("btnh,bcnh->bntc", q, kt) * scale,
                   -jnp.inf)], -1)
    p = jax.nn.softmax(s, -1)
    other = jnp.einsum("bnts,bsnh->btnh", p[..., :t], v) \
        + jnp.einsum("bntc,bcnh->btnh", p[..., t:], vt)
    ours = pk._xla_eva_attention(q, k, v, kt, vt, window, chunk)
    assert float(jnp.max(jnp.abs(ours - other))) > 0.05


def test_pairs_at_the_cells_shape():
    """1 x 16,384 bytes, windows of 2,048, chunks of 16: what ``E`` and
    ``R`` hold, what the kernels' grids score at the blocks they run, and
    what the dense composition would."""
    seq, window, chunk = 16384, 2048, 16
    needed = eva.pairs_needed(seq, window, chunk)
    assert needed == (16_785_408, 7_340_032)
    assert eva.default_blocks(window, chunk) == (512, 512, 256)
    scored = eva.pairs_scored(seq, window, chunk)
    # two windows' summaries a block: odd windows score one window's more
    assert scored == (8 * 10 * 512 * 512, (1 + 1 + 2 + 2 + 3 + 3 + 4)
                      * 256 * 2048)
    assert sum(scored) / sum(needed) == pytest.approx(1.217, abs=1e-3)
    assert sum(scored) / sum(needed) < 1.5
    assert eva.pairs_dense(seq, chunk) / sum(needed) >= 5
    # a window's summaries a block score exactly those needed; four
    # windows' a block four windows' summaries where one is seen
    assert eva.pairs_scored(seq, window, chunk, (512, 512, 128))[1] \
        == needed[1]
    assert eva.pairs_scored(seq, window, chunk, (512, 512, 512))[1] \
        == 10 * 512 * 2048


@pytest.mark.parametrize("case", list(CASES))
def test_pairs_scored_counts_the_grid(case):
    """``pairs_scored`` against a count of the blocks the index ranges of
    the kernels visit, cell by cell."""
    nw, window, chunk, blocks = CASES[case]
    bq, bk, bs = blocks or eva.default_blocks(window, chunk)
    per_window = window // chunk
    exact = summaries = 0
    for w in range(nw):
        for qi in range(window // bq):
            exact += len(range(0, ((qi + 1) * bq + bk - 1) // bk)) * bq * bk
            summaries += len(range(0, (w * per_window + bs - 1) // bs)) \
                * bq * bs
    assert eva.pairs_scored(nw * window, window, chunk, blocks) \
        == (exact, summaries)
    need = eva.pairs_needed(nw * window, window, chunk)
    assert exact >= need[0] and summaries >= need[1]


def test_the_dispatcher_reports_the_path_it_takes(monkeypatch):
    nw, window, chunk = 2, 32, 4
    ops = _operands(nw, window, chunk, batch=1)
    np.testing.assert_array_equal(
        np.asarray(pk.eva_attention(*ops, window, chunk)),
        np.asarray(pk._xla_eva_attention(*ops, window, chunk)))
    # off the chip the dense composition's pairs; where kernels are on,
    # their grids', and the dense count again for a shape they refuse
    assert pk.eva_pairs_scored(64, DIM, window, chunk) == 64 * (64 + 16)
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    assert pk.eva_pairs_scored(64, DIM, window, chunk) == sum(
        eva.pairs_scored(64, window, chunk)) == 2 * 32 * 32 + 32 * 16
    assert pk.eva_pairs_scored(64, 256, window, chunk) == 64 * (64 + 16)
    assert "supports" in pk._refusal("eva_attention_kernel",
                                    fits=(64, 256, window, chunk))


@pytest.mark.parametrize("seq, window, chunk, blocks, ok", [
    (16384, 2048, 16, None, True),
    (16384 + 16, 2048, 16, None, False),     # T not whole windows
    (4096, 2048, 48, None, False),           # window not whole chunks
    (128, 32, 4, (16, 16, 8), True),
    (128, 32, 4, (24, 16, 8), False),        # a block that does not divide
])
def test_supports(seq, window, chunk, blocks, ok):
    assert eva.supports(seq, 128, window, chunk, blocks) is ok
    assert eva.supports(seq, 256, window, chunk, blocks) is False
