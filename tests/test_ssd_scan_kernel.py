"""The ``ssd_scan_fwd`` / ``ssd_scan_bwd`` kernels
(``ops/pallas/ssd_scan_kernel.py``) in interpret mode on the CPU, at small
shapes the kernels accept (64-wide heads, 8 a group, state 128, chunk 128):
against the position-by-position recurrence of ``tests/test_ssd_scan.py``
and against the XLA composition ``_ssd_scan_row``, forward and the gradient
of every operand; what the dispatcher ``ops.pallas.ssd_scan`` takes where,
what it records, and what a compiled step's account says of it."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import _ssd_scan_rows
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import ssd_scan_kernel as sk
from paddle_tpu.ops.registry import raw
from test_ssd_scan import operands, recurrence

kernel = functools.partial(sk.ssd_scan_pallas, interpret=True)
NAMES = ("x", "dt", "A", "B", "C", "D")

# (batch, T, heads, head width, groups, chunk): two chunks; three (no power
# of two) over two groups and two rows; a head a whole tile; four heads a
# tile; a chunk of 256
CASES = {
    "two_chunks": (1, 256, 8, 64, 1, 128),
    "three_chunks_two_groups": (2, 384, 16, 64, 2, 128),
    "head_128": (1, 256, 8, 128, 1, 128),
    "head_32": (1, 256, 8, 32, 1, 128),
    "chunk_256": (1, 512, 8, 64, 1, 256),
}


def _operands(case, dtype=jnp.float32, seed=0):
    batch, t, nh, p, g, chunk = CASES[case]
    x, dt, A, B, C, D = operands(seed + t + nh, batch, t, nh, p, g, 128)
    return (x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype),
            D), chunk


def _grads(fn, ops, chunk, co):
    return jax.grad(lambda *o: jnp.sum(fn(*o, chunk=chunk) * co),
                    argnums=tuple(range(6)))(*ops)


@functools.lru_cache(maxsize=None)
def _kernel_and_composition(case):
    """``(y, gradients)`` of the kernels and of the composition on a case's
    operands under one random cotangent, computed once for the two tests
    that read them."""
    ops, chunk = _operands(case)
    co = jnp.asarray(np.random.default_rng(5).standard_normal(ops[0].shape),
                     jnp.float32)
    out = []
    for fn in (kernel, _ssd_scan_rows):
        y, vjp = jax.vjp(functools.partial(fn, chunk=chunk), *ops)
        out.append((y, vjp(co)))
    return ops, out


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_composition(case):
    """float32 throughout: kernel and composition differ by the order of
    their sums alone (the running sum by log steps for ``cumsum``)."""
    ops, ((got, _), (want, _)) = _kernel_and_composition(case)
    assert got.shape == ops[0].shape and got.dtype == ops[0].dtype
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * scale)


@pytest.mark.parametrize("case", list(CASES))
def test_gradients_of_every_operand_match_the_compositions(case):
    """The hand-written backward against ``jax.grad`` of the composition,
    under one random cotangent; each operand's gradient to 1e-4 of that
    gradient's largest entry."""
    _, ((_, got), (_, want)) = _kernel_and_composition(case)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def _recurrence_rows(x, dt, A, B, C, D, chunk=None):
    return jax.vmap(lambda x, dt, B, C: recurrence(x, dt, A, B, C, D))(
        x, dt, B, C)


def test_kernels_are_the_recurrence():
    """Against the recurrence itself, one position a step: values, and the
    gradients of the recurrence differentiated (three chunks, two groups,
    two rows)."""
    ops, ((got, grads), _) = _kernel_and_composition(
        "three_chunks_two_groups")
    co = jnp.asarray(np.random.default_rng(5).standard_normal(ops[0].shape),
                     jnp.float32)
    want = _recurrence_rows(*ops)
    np.testing.assert_allclose(
        got, want, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    for name, a, b in zip(NAMES, grads,
                          _grads(_recurrence_rows, ops, None, co)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_a_decay_that_would_overflow_above_the_diagonal_stays_finite():
    """Large steps: ``a_i - a_j`` above the diagonal passes 88 (``exp`` of
    it is inf in float32) and ``exp(-a_j)`` alone would overflow anywhere:
    the difference is masked before the exp, forward and backward."""
    (x, dt, A, B, C, D), chunk = _operands("two_chunks")
    dt, A = dt * 0 + 8.0, A * 0 - 4.0
    val, grads = jax.value_and_grad(
        lambda *o: jnp.sum(kernel(*o, chunk=chunk)),
        argnums=tuple(range(6)))(x, dt, A, B, C, D)
    assert np.isfinite(val)
    assert all(np.all(np.isfinite(g)) for g in grads)


def test_bfloat16_operands_keep_float32_sums():
    """bfloat16 x, B, C: y is bfloat16 and as near the float32 result as
    the composition's in bfloat16 (operands rounded, sums float32: 3e-2 of
    the scale), and kernel and composition round at the same places."""
    ops, chunk = _operands("two_chunks", jnp.bfloat16)
    exact, _ = _operands("two_chunks")
    low = kernel(*ops, chunk=chunk)
    assert low.dtype == jnp.bfloat16
    want = _ssd_scan_rows(*exact, chunk=chunk)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - want))) \
        < 3e-2 * scale
    composed = _ssd_scan_rows(*ops, chunk=chunk).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - composed))) \
        < 1e-2 * scale
    grads = _grads(kernel, ops, chunk, jnp.ones(ops[0].shape, jnp.float32))
    assert [g.dtype for g in grads] == [o.dtype for o in ops]


def test_the_state_is_carried_from_chunk_to_chunk():
    """Chunk 0's x changes chunk 1's and chunk 2's output (through the
    state alone; steps small enough that a chunk does not decay it away)
    and nothing of another row; a later chunk's x changes nothing before
    it."""
    (x, dt, *rest), chunk = _operands("three_chunks_two_groups")
    ops = (x, dt * 0.05, *rest)
    base = kernel(*ops, chunk=chunk)
    early = kernel(x.at[0, :chunk].add(1.0), *ops[1:], chunk=chunk)
    for c in (1, 2):
        rows = slice(c * chunk, (c + 1) * chunk)
        assert float(jnp.max(jnp.abs(early[0, rows] - base[0, rows]))) > 1e-3
    np.testing.assert_array_equal(early[1], base[1])
    late = kernel(x.at[0, 2 * chunk:].add(1.0), *ops[1:], chunk=chunk)
    np.testing.assert_array_equal(late[0, :2 * chunk], base[0, :2 * chunk])


# ---------------------------------------------------------- the dispatcher --

REFUSED = {
    "chunk_16": (64, 8, 64, 1, 128, 16, jnp.float32),
    "state_16": (256, 8, 64, 1, 16, 128, jnp.float32),
    "head_8": (256, 16, 8, 1, 128, 128, jnp.float32),
    "four_heads_a_group": (256, 8, 64, 2, 128, 128, jnp.float32),
    "half_a_chunk_over": (320, 8, 64, 1, 128, 128, jnp.float32),
    "float16": (256, 8, 64, 1, 128, 128, jnp.float16),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_supports_refuses_what_the_kernels_cannot_tile(case):
    assert not sk.supports(*REFUSED[case])
    t, nh, p, g, n, chunk, dtype = REFUSED[case]
    if t % chunk == 0:
        x, dt, A, B, C, D = operands(1, 1, t, nh, p, g, n)
        with pytest.raises(ValueError, match="supports"):
            sk.ssd_scan_pallas(x.astype(dtype), dt, A, B.astype(dtype),
                               C.astype(dtype), D, chunk=chunk)


def test_supports_takes_the_published_shapes():
    assert sk.supports(4096, 128, 64, 8, 128, 128, jnp.bfloat16)
    assert sk.supports(8192, 128, 64, 8, 128, 256, jnp.float32)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatcher sees on the chip: kernels on, the backend's name
    ``tpu``, and the kernels themselves in interpret mode."""
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sk, "ssd_scan_pallas", kernel)


def test_off_the_tpu_the_composition_runs_without_a_word():
    ops, chunk = _operands("two_chunks")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = pk.ssd_scan(*ops, chunk)
    np.testing.assert_array_equal(got, _ssd_scan_rows(*ops, chunk=chunk))
    rec = pk.ssd_scan_log()[-1]
    assert rec["path"] == "composition" and "no TPU" in rec["reason"]
    assert rec["shapes"] == ((1, 256, 8, 64), (1, 256, 1, 128))


def test_on_the_tpu_the_kernels_run_and_are_recorded(on_tpu):
    ops, chunk = _operands("two_chunks")
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = pk.ssd_scan(*ops, chunk)
    want = _ssd_scan_rows(*ops, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))
    rec = pk.ssd_scan_log()[-1]
    assert (rec["path"], rec["reason"], rec["chunk"]) == ("kernel", None, 128)
    after = pk.traced_call_sums()
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 0, "flash_operands_in_place": 0,
        "flash_operands_copied": 0, "ssd_calls": 1, "ssd_calls_composed": 0,
        "mla_expand_calls": 0, "mla_expand_calls_composed": 0,
        "moe_run_sum_calls": 0, "moe_run_sum_calls_composed": 0,
        "causal_conv_calls": 0, "causal_conv_calls_composed": 0,
        "gated_norm_calls": 0, "gated_norm_calls_composed": 0,
        "cca_mix_calls": 0, "cca_mix_calls_composed": 0}


@pytest.mark.parametrize("case", ["chunk_16", "state_16", "head_8"])
def test_on_the_tpu_a_refused_shape_takes_the_composition_aloud(on_tpu,
                                                                 case):
    """Through ``F.ssd_scan``: the refusal warns and the composition's
    values come back."""
    t, nh, p, g, n, chunk, _ = REFUSED[case]
    ops = operands(3, 1, t, nh, p, g, n)
    before = pk.traced_call_sums()
    with pytest.warns(pk.KernelFallbackWarning, match="ssd_scan.*supports"):
        got = raw("ssd_scan")(*ops, chunk=chunk)
    np.testing.assert_allclose(got, _recurrence_rows(*ops), rtol=2e-5,
                               atol=2e-4)
    rec = pk.ssd_scan_log()[-1]
    assert rec["path"] == "composition" and "supports" in rec["reason"]
    after = pk.traced_call_sums()
    assert (after["ssd_calls"] - before["ssd_calls"],
            after["ssd_calls_composed"] - before["ssd_calls_composed"]) \
        == (1, 1)


def test_a_ragged_row_is_padded_and_takes_the_kernels(on_tpu):
    """``F.ssd_scan`` pads 200 positions to two chunks with steps of size
    zero, which leave the state as it is; the kernels run on the padded
    rows, and values and gradients are the recurrence's on the 200."""
    ops = operands(9, 1, 200, 8, 64, 1, 128)
    x, dt, A, B, C, D = ops
    co = jnp.asarray(np.random.default_rng(3).standard_normal(x.shape),
                     jnp.float32)
    plain = _recurrence_rows

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = raw("ssd_scan")(*ops, chunk=128)
        grads = _grads(raw("ssd_scan"), ops, 128, co)
    assert pk.ssd_scan_log()[-1]["path"] == "kernel"
    assert pk.ssd_scan_log()[-1]["shapes"][0] == (1, 256, 8, 64)
    want = plain(*ops)
    assert got.shape == x.shape
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))
    for name, a, b in zip(NAMES, grads, _grads(plain, ops, 128, co)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_under_a_gspmd_mesh_the_composition_runs_aloud(on_tpu):
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh

    ops, chunk = _operands("two_chunks")
    with use_mesh(build_mesh(dp=2, devices=jax.devices()[:2])):
        with pytest.warns(pk.KernelFallbackWarning, match="GSPMD"):
            got = pk.ssd_scan(*ops, chunk)
    np.testing.assert_array_equal(got, _ssd_scan_rows(*ops, chunk=chunk))
    assert pk.ssd_scan_log()[-1]["reason"].startswith(pk.GSPMD_REASON)


def test_the_compiled_steps_account_counts_its_scans():
    """``TrainStep.compile_account()`` over a ``nemotron_h_tiny`` of two
    Mamba blocks: their scans traced while the step compiled, both the
    composition's off the TPU (and at the tiny model's chunk of 16, which
    the kernels refuse)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.nemotron_h import nemotron_h_tiny

    paddle.seed(0)
    model = nemotron_h_tiny(num_hidden_layers=2,
                            hybrid_override_pattern="MM")
    step = TrainStep(
        model, lambda logits, labels: model.loss(logits, labels),
        paddle.optimizer.AdamW(learning_rate=1e-3,
                               parameters=model.parameters()))
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 512, (1, 32)).astype(np.int32))
    step(ids, ids)
    account = step.compile_account()
    assert (account["ssd_calls"], account["ssd_calls_composed"]) == (2, 2)
    assert account["flash_calls"] == 0
    rec = pk.ssd_scan_log()[-1]
    assert rec["path"] == "composition" and rec["chunk"] == 16
    assert rec["shapes"] == ((1, 32, 8, 8), (1, 32, 2, 16))
