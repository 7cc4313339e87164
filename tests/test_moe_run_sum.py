"""The expert layer's way back to its tokens (PR 45): the ``moe_run_sum``
kernel in interpret mode against the XLA composition (``dropless._run_sums``)
and against the sum by slots, the dispatcher ``ops.pallas.moe_run_sum`` and
its counter, and ``sort_by_expert``'s permutations, which come out of sorts,
against the scatter they replaced.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import moe_run_sum_kernel as runs

# (tokens, k, held, experts, width): the four expert cells' routed blocks
# cut down (sdar top-8 with 16 of 128 held, kanana top-6 with 16 of 128,
# laguna top-10 with 8 of 256, the hybrid top-22 with 8 of 512 in a latent),
# the number of tokens and the rows' width cut, the ratios kept where a
# small size can
CELLS = {"sdar": (512, 8, 16, 128, 256), "kanana": (512, 6, 16, 128, 256),
         "laguna": (512, 10, 8, 64, 384), "hybrid": (256, 22, 8, 64, 128)}


def _routing(tokens, top_k, held, experts, seed=0, offset=None):
    """Seeded distinct experts a token, and what the routed block makes of
    them: ``(idx, weights, order, w_sorted, here, counts)``."""
    rng = np.random.RandomState(seed)
    idx = jnp.asarray(np.argsort(rng.rand(tokens, experts), axis=1)
                      [:, :top_k], jnp.int32)
    weights = jnp.asarray(rng.rand(tokens, top_k) + 0.1, jnp.float32)
    return (idx, weights) + _sorted(idx, weights,
                                    experts - held if offset is None
                                    else offset, held)


def _sorted(idx, weights, offset, held):
    tokens, top_k = idx.shape
    key = dropless.expert_keys(idx, offset, held)
    _, order, w_sorted = dropless._sorted_by(key, weights.T.reshape(-1))
    here = jnp.sum((key < held).reshape(top_k, tokens), axis=0,
                   dtype=jnp.int32)
    return order, w_sorted, here, dropless.group_sizes(key, held)


def _bucket(order, w_sorted, counts, tokens, rows, width, dtype, seed=0,
            behind=np.nan):
    """``rows`` sorted rows of ``width``, NaN behind the last group, each
    row's token (``tokens`` behind the last group) and weight."""
    total = int(counts.sum())
    assert total <= rows
    data = np.full((rows, width), behind, np.float32)
    data[:total] = np.random.RandomState(seed).randn(total, width)
    token = jnp.where(jnp.arange(rows) < total, order[:rows] % tokens, tokens)
    return jnp.asarray(data, dtype), token, w_sorted[:rows]


def _kernel(block=None):
    return functools.partial(runs.moe_run_sum_pallas, interpret=True,
                             block=block)


def _sum_by_runs(monkeypatch, run_sums, *args):
    """``dropless._sum_by_runs`` with ``run_sums`` in the dispatcher's
    place: the kernel in interpret mode, or the composition."""
    monkeypatch.setattr(pk, "moe_run_sum", run_sums)
    return dropless._sum_by_runs(*args)


def _assert_same(got, want, weighted):
    """Bit for bit without weights; with them the CPU's compiler contracts
    the composition's product and first add into one rounding here and
    there (the TPU's has no such instruction): one unit of the last place
    of a float32 term."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if weighted:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernel_matches_the_composition_bit_for_bit(cell, weighted,
                                                        dtype, monkeypatch):
    """At the four cells' routed blocks cut down, in their small bucket
    (twice the rows expected, five times in the hybrid cell): the kernel in
    interpret mode gives what ``_run_sums`` gives, and both what the sum by
    slots gives up to the order of a token's float32 adds; NaN behind the
    last group reaches no token."""
    tokens, top_k, held, experts, width = CELLS[cell]
    idx, weights, order, w_sorted, here, counts = _routing(
        tokens, top_k, held, experts)
    headroom = 5 if cell == "hybrid" else 2
    rows = -(-headroom * tokens * top_k * held // experts // 64) * 64
    data, token, w_rows = _bucket(order, w_sorted, counts, tokens, rows,
                                  width, dtype)
    max_run = min(top_k, held)
    assert runs.supports(rows, width, max_run, dtype)
    args = (data, token, w_rows if weighted else None, here, max_run)
    got = _sum_by_runs(monkeypatch, _kernel(), *args)
    want = _sum_by_runs(monkeypatch, dropless._run_sums, *args)
    assert got.shape == (tokens, width) and got.dtype == data.dtype
    assert np.isfinite(np.asarray(want, np.float32)).all()
    _assert_same(got, want, weighted)
    # and the parent's form, one row gathered a slot
    _, inverse, _ = dropless.sort_by_expert(idx, experts - held, held)
    slots = dropless._sum_by_slots(data, weights if weighted else None,
                                   inverse, counts, tokens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(slots, np.float32), rtol=tol,
                               atol=tol)
    assert int(here.max()) >= 2          # runs of several rows are there


@pytest.mark.parametrize("block", [(16, 128), (32, 256), (64, 128),
                                   (256, 256)])
def test_runs_that_straddle_a_row_block(block, monkeypatch):
    """Row blocks of 16 to 256 rows under runs of up to 8: most blocks end
    inside a run, whose rest the kernel reads from the halo; the last
    block's halo is clamped and never read."""
    tokens, top_k, held, experts, width = 128, 8, 8, 10, 256
    _, _, order, w_sorted, here, counts = _routing(tokens, top_k, held,
                                                   experts, seed=3)
    rows = 1024
    data, token, w_rows = _bucket(order, w_sorted, counts, tokens, rows,
                                  width, jnp.float32)
    token_sorted = np.sort(np.asarray(token))
    ends = token_sorted[block[0] - 1:-1:block[0]]
    starts = token_sorted[block[0]::block[0]]
    assert (ends == starts)[starts < tokens].sum() >= 2     # runs straddle
    args = (data, token, w_rows, here, min(top_k, held))
    _assert_same(_sum_by_runs(monkeypatch, _kernel(block), *args),
                 _sum_by_runs(monkeypatch, dropless._run_sums, *args), True)


@pytest.mark.parametrize("case", ["tokens_with_no_row_here",
                                  "every_assignment_elsewhere",
                                  "every_assignment_local",
                                  "one_expert_takes_all"])
def test_degenerate_routings(case, monkeypatch):
    """Tokens none of whose experts is held get zero, not a neighbour's
    row; a layer that serves nothing gives zeros out of a bucket of NaN;
    the worst-case bucket (every assignment local) is summed the same
    way."""
    tokens, top_k, width = 96, 4, 128
    rng = np.random.RandomState(7)
    held, experts, offset = {"tokens_with_no_row_here": (2, 16, 3),
                             "every_assignment_elsewhere": (4, 16, 12),
                             "every_assignment_local": (4, 4, 0),
                             "one_expert_takes_all": (4, 16, 5)}[case]
    idx = np.argsort(rng.rand(tokens, experts), axis=1)[:, :top_k]
    if case == "every_assignment_elsewhere":
        idx = np.argsort(rng.rand(tokens, 12), axis=1)[:, :top_k]
    if case == "one_expert_takes_all":
        idx[:, 0] = offset
        idx[:, 1:] = 9 + np.argsort(rng.rand(tokens, 7), axis=1)[:, :3]
    idx = jnp.asarray(idx, jnp.int32)
    weights = jnp.asarray(rng.rand(tokens, top_k) + 0.1, jnp.float32)
    order, w_sorted, here, counts = _sorted(idx, weights, offset, held)
    rows = dropless.sorted_rows(tokens, top_k, held)
    data, token, w_rows = _bucket(order, w_sorted, counts, tokens, rows,
                                  width, jnp.float32)
    args = (data, token, w_rows, here, min(top_k, held))
    got = _sum_by_runs(monkeypatch, _kernel((32, 128)), *args)
    _assert_same(got, _sum_by_runs(monkeypatch, dropless._run_sums, *args),
                 True)
    _, inverse, _ = dropless.sort_by_expert(idx, offset, held)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dropless._sum_by_slots(
            data, weights, inverse, counts, tokens)), rtol=1e-5, atol=1e-6)
    none_here = np.asarray(here) == 0
    assert (np.asarray(got)[none_here] == 0).all()
    assert none_here.sum() == {"tokens_with_no_row_here": none_here.sum(),
                               "every_assignment_elsewhere": tokens,
                               "every_assignment_local": 0,
                               "one_expert_takes_all": 0}[case]
    if case == "tokens_with_no_row_here":
        assert 0 < none_here.sum() < tokens


@pytest.mark.parametrize("rows,width,max_run,dtype,serves", [
    (32768, 2048, 8, jnp.bfloat16, True),      # sdar's small bucket
    (24576, 2048, 6, jnp.bfloat16, True),      # kanana's
    (5120, 3072, 8, jnp.bfloat16, True),       # laguna's
    (7168, 1024, 8, jnp.bfloat16, True),       # the hybrid cell's
    (131072, 2048, 8, jnp.bfloat16, True),     # sdar's worst case
    (512, 128, 16, jnp.float32, True),
    (512, 96, 8, jnp.bfloat16, False),         # lanes off the tiles
    (520, 128, 8, jnp.bfloat16, False),        # no block of 16 rows divides
    (512, 128, 17, jnp.bfloat16, False),       # a run longer than the halo
    (512, 128, 8, jnp.float16, False),
])
def test_supports_reads_the_shapes_alone(rows, width, max_run, dtype, serves):
    assert runs.supports(rows, width, max_run, dtype) == serves
    if not serves and dtype != jnp.float16:
        with pytest.raises(ValueError, match="moe run sum does not serve"):
            runs.moe_run_sum_pallas(
                jnp.zeros((rows, width), dtype), jnp.zeros(rows, jnp.int32),
                max_run=max_run, interpret=True)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatcher sees on the chip; the kernel itself in interpret
    mode."""
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(runs, "moe_run_sum_pallas", _kernel())


@pytest.mark.parametrize("case", ["kernel", "off_the_tpu", "width_off_tiles",
                                  "mesh"])
def test_the_dispatch_is_counted_and_gives_way_aloud(case, request):
    """``ops.pallas.moe_run_sum``: the kernel where ``supports()`` says yes
    on a TPU; ``dropless._run_sums`` with a warning that says why for a
    width off the 128-lane tiles and under a mesh GSPMD partitions, and
    without a word off the TPU.  Every call is in ``moe_run_sum_log()`` and
    in ``traced_call_sums()``'s two counts."""
    from jax.sharding import Mesh

    from paddle_tpu.distributed.fleet.spmd import use_mesh

    if case != "off_the_tpu":
        request.getfixturevalue("on_tpu")
    rows, width = 64, 96 if case == "width_off_tiles" else 128
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randn(rows, width), jnp.float32)
    rem = jnp.asarray(np.tile([2, 1, 0, 0], rows // 4), jnp.int32)
    call = functools.partial(pk.moe_run_sum, data, rem, None, max_run=3)
    before = pk.traced_call_sums()
    if case in ("kernel", "off_the_tpu"):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = call()
        reason = None if case == "kernel" else pk.NO_TPU
    elif case == "width_off_tiles":
        with pytest.warns(pk.KernelFallbackWarning,
                          match="moe_run_sum.*supports"):
            got = call()
        reason = "moe_run_sum_kernel.supports() refuses the shape"
    else:
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        with use_mesh(mesh), pytest.warns(
                pk.KernelFallbackWarning,
                match="moe_run_sum.*GSPMD cannot partition a Mosaic kernel"):
            got = jax.eval_shape(call)
        reason = pk.GSPMD_REASON
    after = pk.traced_call_sums()
    rec = pk.moe_run_sum_log()[-1]
    assert rec["path"] == ("kernel" if case == "kernel" else "composition")
    assert rec["reason"] is reason is None or reason in rec["reason"]
    assert (rec["shapes"], rec["max_run"], rec["weighted"]) \
        == ((rows, width), 3, False)
    assert {k: after[k] - before[k] for k in after if after[k] != before[k]} \
        == {"moe_run_sum_calls": 1,
            **({} if case == "kernel" else {"moe_run_sum_calls_composed": 1})}
    if case != "mesh":
        want = np.asarray(data).reshape(-1, 4, width)
        want = want[:, 0] + want[:, 1] + want[:, 2]
        np.testing.assert_allclose(np.asarray(got)[::4], want, rtol=1e-6)


def test_the_routed_block_counts_two_calls_a_traced_branch(on_tpu):
    """One call for combine and one for the dispatch's transpose in every
    branch of the two switches; none composed where the shapes fit."""
    tokens, top_k, held, experts, width = 1024, 4, 4, 32, 128
    idx, weights, *_ = _routing(tokens, top_k, held, experts)
    buckets = dropless.row_buckets(tokens, top_k, held, experts)
    assert len(buckets) == 2
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(tokens, width), jnp.float32)
    w_in = jnp.asarray(rng.randn(held, width, 64) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.randn(held, 32, width) * 0.1, jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error", pk.KernelFallbackWarning)
        jax.jit(jax.grad(lambda *a: jnp.sum(dropless.routed_experts(
            *a, idx, experts - held, buckets)), argnums=(0, 1, 2, 3))
        ).trace(x, weights, w_in, w_out)
    after = pk.traced_call_sums()
    assert after["moe_run_sum_calls"] - before["moe_run_sum_calls"] == 4
    assert after["moe_run_sum_calls_composed"] \
        == before["moe_run_sum_calls_composed"]


# ------------------------------------------- the permutations ride the sort --

def _sort_by_scatter(idx, expert_offset, num_local):
    """``sort_by_expert`` as the parent (PR 44) wrote it: ``argsort`` and a
    scatter of the iota."""
    tokens, top_k = idx.shape
    local = idx.T.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < num_local), local, num_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    counts = jnp.sum(key[:, None] == jnp.arange(num_local)[None, :], axis=0,
                     dtype=jnp.int32)
    return order[:dropless.sorted_rows(tokens, top_k, num_local)], inverse, \
        counts


@pytest.mark.parametrize("case", ["random", "cut", "all_to_one_held",
                                  "none_held", "all_held", "one_token"])
def test_sort_by_expert_is_the_parents_bit_for_bit(case):
    tokens, top_k, experts, offset, held = {
        "random": (200, 6, 32, 5, 8), "cut": (128, 10, 64, 8, 4),
        "all_to_one_held": (64, 3, 16, 2, 4), "none_held": (64, 3, 16, 12, 4),
        "all_held": (48, 4, 8, 0, 8), "one_token": (1, 8, 16, 4, 8)}[case]
    rng = np.random.RandomState(len(case))
    idx = np.argsort(rng.rand(tokens, experts), axis=1)[:, :top_k]
    if case == "all_to_one_held":
        idx[:, 0] = offset + 1
    if case == "none_held":
        idx = np.argsort(rng.rand(tokens, 12), axis=1)[:, :top_k]
    idx = jnp.asarray(idx, jnp.int32)
    got = jax.jit(dropless.sort_by_expert, static_argnums=(1, 2))(
        idx, offset, held)
    want = _sort_by_scatter(idx, offset, held)
    for g, w, name in zip(got, want, ("order", "inverse", "counts")):
        assert g.dtype == w.dtype == jnp.int32 and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    # no scatter and no gather is left in it
    text = jax.jit(dropless.sort_by_expert, static_argnums=(1, 2)).lower(
        idx, offset, held).as_text()
    assert "scatter" not in text and "gather" not in text
    assert text.count("stablehlo.sort") == 2


@pytest.mark.parametrize("body,cell", [("swiglu", "sdar"), ("relu2", "hybrid"),
                                       ("swiglu", "laguna"),
                                       ("relu2", "kanana")])
def test_grad_of_the_routed_block_against_the_dense_reference(body, cell,
                                                              monkeypatch):
    """``jax.grad`` of ``routed_experts`` with the kernel in the
    dispatcher's place (interpret mode; the grouped matmuls stay the
    CPU's), both bodies, against a dense loop over the experts held; the
    weights' gradient is back at its ``[S, k]`` slots, zero where the
    expert is held elsewhere."""
    calls = []

    def kernel(rows, rem, weights=None, *, max_run):
        calls.append(rows.shape)
        return _kernel()(rows, rem, weights, max_run=max_run)

    monkeypatch.setattr(pk, "moe_run_sum", kernel)
    tokens, top_k, held, experts, _ = CELLS[cell]
    tokens, width, inner, offset = tokens // 4, 128, 32, 3
    idx, weights, *_ = _routing(tokens, top_k, held, experts, seed=2,
                                offset=offset)
    rng = np.random.RandomState(4)
    f32 = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    x, probe = f32(tokens, width), f32(tokens, width)
    w_in = f32(held, width, dropless.BODIES[body][1] * inner) * 0.2
    w_out = f32(held, inner, width) * 0.2
    buckets = dropless.row_buckets(tokens, top_k, held, experts)

    def block(x, weights, w_in, w_out):
        return jnp.sum(probe * dropless.routed_experts(
            x, weights, w_in, w_out, idx, offset, buckets, body))

    def dense(x, weights, w_in, w_out):
        out = 0.0
        for e in range(held):
            w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=1)
            out = out + w_e[:, None] * (
                dropless.BODIES[body][0](x @ w_in[e]) @ w_out[e])
        return jnp.sum(probe * out)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(block, argnums=(0, 1, 2, 3))(
            x, weights, w_in, w_out)
        want = jax.value_and_grad(dense, argnums=(0, 1, 2, 3))(
            x, weights, w_in, w_out)
    assert len(calls) == 2 * len(buckets)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for name, a, b in zip(("x", "weights", "w_in", "w_out"), got[1], want[1]):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * scale,
                                   err_msg=name)
    elsewhere = (np.asarray(idx) < offset) | (np.asarray(idx) >= offset + held)
    assert (np.asarray(got[1][1])[elsewhere] == 0).all()
