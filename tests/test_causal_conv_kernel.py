"""The ``causal_conv_fwd`` / ``causal_conv_bwd`` kernels
(``ops/pallas/causal_conv_kernel.py``) in interpret mode on the CPU against
the XLA composition ``F.silu(F.causal_conv1d(x, w, b))``, values and the
gradients of x, w and b; where a row starts and where a batch row ends;
what the dispatcher ``ops.pallas.causal_conv1d`` takes where, what it
records, and what a compiled step's account says of it."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import _causal_conv1d_silu
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import causal_conv_kernel as ck
from paddle_tpu.ops.registry import raw

kernel = functools.partial(ck.causal_conv_pallas, interpret=True)
composition = jax.jit(_causal_conv1d_silu)
NAMES = ("x", "weight", "bias")

# (batch, T, channels, taps, block): one row block, found by the kernels'
# own rule; several row blocks and lane blocks of two chunks each, two batch
# rows; blocks of the halo's own 16 rows, so every block's edge lies inside
# the taps' reach and a chunk's halo is all there is of the block before;
# the most taps the kernels serve, and the fewest
CASES = {
    "one_block": (1, 64, 256, 4, None),
    "several_blocks": (2, 128, 256, 4, (32, 128, 16, 128)),
    "edge_in_reach": (1, 48, 128, 4, (16, 128, 16, 128)),
    "seven_taps": (1, 64, 128, 7, (32, 128, 16, 128)),
    "one_tap": (1, 32, 128, 1, (16, 128, 16, 128)),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# values: float32 differs by the order the compiler gives the sums; in
# bfloat16 the kernels round once, the result, where the CPU's composition
# rounds the sum, the sigmoid's three steps and the product (on the TPU it
# rounds once too), and the interpreter stands in for the EUP's approximate
# reciprocal with a bfloat16 one: two units in the last of 8 bits
VALUE_TOL = {"float32": 2e-6, "bfloat16": 1.6e-2}
# gradients, of each operand's largest entry: the scan kernels' 1e-4 in
# float32; in bfloat16 the roundings inside the composition's derivative
GRAD_TOL = {"float32": 1e-4, "bfloat16": 2e-2}


def _operands(case, dtype, bias=True, seed=0):
    batch, t, channels, taps, block = CASES[case]
    r = np.random.default_rng(seed + t + channels + taps)
    bound = taps ** -0.5
    x = jnp.asarray(r.standard_normal((batch, t, channels)), dtype)
    w = jnp.asarray(r.uniform(-bound, bound, (channels, taps)), dtype)
    b = jnp.asarray(r.uniform(-bound, bound, (channels,)), dtype) \
        if bias else None
    return (x, w, b), block


@functools.lru_cache(maxsize=None)
def _kernel_and_composition(case, dtype, bias):
    """``(y, gradients)`` of the kernels and of the composition on a case's
    operands under one random cotangent, computed once for the two tests
    that read them."""
    ops, block = _operands(case, DTYPES[dtype], bias)
    co = jnp.asarray(np.random.default_rng(5).standard_normal(ops[0].shape),
                     ops[0].dtype)
    out = []
    for fn in (functools.partial(kernel, block=block), composition):
        y, vjp = jax.vjp(fn, *ops)
        out.append((y, vjp(co)))
    return ops, out


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_composition(case, dtype, bias):
    ops, ((got, _), (want, _)) = _kernel_and_composition(case, dtype, bias)
    assert got.shape == ops[0].shape and got.dtype == ops[0].dtype
    tol = VALUE_TOL[dtype]
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(CASES))
def test_gradients_of_every_operand_match_the_compositions(case, dtype,
                                                           bias):
    """The hand-written backward against ``jax.grad`` of the composition,
    under one random cotangent."""
    _, ((_, got), (_, want)) = _kernel_and_composition(case, dtype, bias)
    tol = GRAD_TOL[dtype]
    for name, a, b in zip(NAMES, got, want):
        if b is None:
            assert a is None and name == "bias" and not bias
            continue
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            _f32(a), _f32(b), rtol=tol,
            atol=tol * float(np.max(np.abs(_f32(b)))), err_msg=name)


@pytest.mark.parametrize("case", ["several_blocks", "seven_taps"])
def test_positions_before_a_rows_first_read_zero(case):
    """The first ``K - 1`` outputs of every batch row are the taps' sums
    over the positions that exist, written out by hand."""
    (x, w, b), block = _operands(case, jnp.float32)
    taps = w.shape[1]
    got = kernel(x, w, b, block=block)
    for t in range(taps - 1):
        pre = b + sum(w[:, k] * x[:, t - (taps - 1) + k]
                      for k in range(taps) if t - (taps - 1) + k >= 0)
        np.testing.assert_allclose(got[:, t], jax.nn.silu(pre), rtol=2e-6,
                                   atol=2e-6)


def test_two_batch_rows_do_not_see_each_other():
    """Another first row leaves the second row's values and its x's
    gradient as they were, bit for bit (the end of row 0 is not the halo of
    row 1; the start of row 1 is not behind the end of row 0)."""
    (x, w, b), block = _operands("several_blocks", jnp.float32)
    fn = functools.partial(kernel, block=block)
    other = x.at[0].add(1.0)
    co = jnp.ones_like(x)
    (y0, vjp0), (y1, vjp1) = jax.vjp(fn, x, w, b), jax.vjp(fn, other, w, b)
    assert float(jnp.max(jnp.abs(y1[0] - y0[0]))) > 1e-3
    np.testing.assert_array_equal(y1[1], y0[1])
    np.testing.assert_array_equal(vjp1(co)[0][1], vjp0(co)[0][1])


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_channels_are_read_where_they_lie_in_a_wider_operand(dtype):
    """``start=``: the convolution of lanes ``start .. start + C`` of a
    wider x is the convolution of that slice, bit for bit, and x's gradient
    is the slice's padded with zeros (two lane blocks in, one before them)."""
    (wide, w, b), _ = _operands("several_blocks", DTYPES[dtype])
    w, b = w[:128], b[:128]
    co = jnp.asarray(np.random.default_rng(5).standard_normal(
        wide.shape[:2] + (128,)), wide.dtype)
    block = (32, 128, 16, 128)
    y, vjp = jax.vjp(functools.partial(kernel, start=128, block=block),
                     wide, w, b)
    want, want_vjp = jax.vjp(functools.partial(kernel, block=block),
                             wide[..., 128:], w, b)
    np.testing.assert_array_equal(_f32(y), _f32(want))
    (dx, dw, db), (want_dx, want_dw, want_db) = vjp(co), want_vjp(co)
    assert dx.shape == wide.shape and dx.dtype == wide.dtype
    np.testing.assert_array_equal(_f32(dx[..., 128:]), _f32(want_dx))
    np.testing.assert_array_equal(_f32(dx[..., :128]), 0.0)
    np.testing.assert_array_equal(_f32(dw), _f32(want_dw))
    np.testing.assert_array_equal(_f32(db), _f32(want_db))
    composed = _causal_conv1d_silu(wide, w, b, 128)
    np.testing.assert_allclose(_f32(y), _f32(composed), rtol=VALUE_TOL[dtype],
                               atol=VALUE_TOL[dtype])


def test_a_later_position_changes_nothing_before_it():
    (x, w, b), block = _operands("several_blocks", jnp.float32)
    late = x.at[:, 64:].add(1.0)
    np.testing.assert_array_equal(kernel(late, w, b, block=block)[:, :64],
                                  kernel(x, w, b, block=block)[:, :64])


def test_bfloat16_operands_keep_float32_sums():
    """256 + 1 + 1 + 1: a bfloat16 running sum stays at 256 (the next value
    is 258), the float32 sum is 259 and rounds ONCE, to 260; and the output
    and every gradient come back in the operands' dtype."""
    bf16 = jnp.bfloat16
    x = jnp.ones((1, 32, 128), bf16).at[0, 0].set(256.0)
    w = jnp.ones((128, 4), bf16)
    got = kernel(x, w, None)
    assert got.dtype == bf16
    lossy = functools.reduce(lambda a, v: a + v, [x[0, t] for t in range(4)])
    assert float(lossy[0]) == 256.0
    np.testing.assert_array_equal(_f32(got[0, 3]), 260.0)
    np.testing.assert_allclose(_f32(got), _f32(composition(x, w, None)),
                               rtol=VALUE_TOL["bfloat16"])
    (x, w, b), _ = _operands("one_block", bf16)
    grads = jax.grad(lambda *o: jnp.sum(kernel(*o).astype(jnp.float32)),
                     argnums=(0, 1, 2))(x, w, b)
    assert [g.dtype for g in grads] == [bf16] * 3


# ---------------------------------------------------------- the dispatcher --

# (T, channels, taps, dtype, start)
REFUSED = {
    "channels_96": (64, 96, 4, jnp.bfloat16, 0),
    "nine_taps": (64, 128, 9, jnp.bfloat16, 0),
    "int8": (64, 128, 4, jnp.int8, 0),
    "rows_off_the_tiles": (24, 128, 4, jnp.float32, 0),
    "start_off_the_tiles": (64, 128, 4, jnp.float32, 64),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_supports_refuses_what_the_kernels_cannot_tile(case):
    assert not ck.supports(*REFUSED[case])
    t, channels, taps, dtype, start = REFUSED[case]
    with pytest.raises(ValueError, match="supports"):
        ck.causal_conv_pallas(jnp.ones((1, t, start + channels), dtype),
                              jnp.ones((channels, taps), dtype), start=start)


def test_supports_takes_the_published_shapes():
    assert ck.supports(4096, 10240, 4, jnp.bfloat16)
    assert ck.supports(8192, 10240, 4, jnp.float32)
    # x, B and C where they lie in the projection's [T, 18560]
    for channels, start in ((8192, 8192), (1024, 16384), (1024, 17408)):
        assert ck.supports(4096, channels, 4, jnp.bfloat16, start)
        assert ck._pick_block(4096, channels, jnp.bfloat16, start) \
            == (512, 1024, 128, 128)
    # a start that only smaller lane blocks divide
    assert ck._pick_block(4096, 1024, jnp.bfloat16, 384)[1] == 128
    # the tiny test config's convolution: 8 * 8 + 2 * 2 * 16 channels
    assert ck.supports(64, 128, 4, jnp.float32)


def test_channels_beyond_the_operand_are_refused():
    with pytest.raises(ValueError, match="supports"):
        ck.causal_conv_pallas(jnp.ones((1, 64, 256)), jnp.ones((256, 4)),
                              start=128)


def test_a_block_that_does_not_tile_is_refused():
    (x, w, b), _ = _operands("one_block", jnp.float32)
    with pytest.raises(ValueError, match="does not tile"):
        kernel(x, w, b, block=(48, 128, 16, 128))


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatcher sees on the chip: kernels on, the backend's name
    ``tpu``, and the kernels themselves in interpret mode."""
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ck, "causal_conv_pallas", kernel)


def _sums_moved(before):
    after = pk.traced_call_sums()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def test_off_the_tpu_the_composition_runs_without_a_word():
    (x, w, b), _ = _operands("one_block", jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = raw("causal_conv1d")(x, w, b, activation="silu")
    np.testing.assert_array_equal(got, _causal_conv1d_silu(x, w, b))
    rec = pk.causal_conv_log()[-1]
    assert rec["path"] == "composition" and "no TPU" in rec["reason"]
    assert (rec["shapes"], rec["start"]) == (((1, 64, 256), (256, 4)), 0)
    assert _sums_moved(before) == {"causal_conv_calls": 1,
                                   "causal_conv_calls_composed": 1}


def test_without_an_activation_nothing_is_dispatched(on_tpu):
    """``activation=None`` is the plain composition, on the TPU too: no
    record, no count, and ``silu`` of it is the fused call's composition."""
    (x, w, b), _ = _operands("one_block", jnp.float32)
    before = pk.traced_call_sums()
    plain = raw("causal_conv1d")(x, w, b)
    assert _sums_moved(before) == {}
    np.testing.assert_array_equal(jax.nn.silu(plain),
                                  _causal_conv1d_silu(x, w, b))
    np.testing.assert_array_equal(
        raw("causal_conv1d")(x, w[128:], b[128:], start=128),
        plain[..., 128:])
    with pytest.raises(ValueError, match="neither None nor 'silu'"):
        raw("causal_conv1d")(x, w, b, activation="gelu")


def test_on_the_tpu_the_kernels_run_and_are_recorded(on_tpu):
    (x, w, b), _ = _operands("one_block", jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = raw("causal_conv1d")(x, w, b, activation="silu")
    np.testing.assert_allclose(got, composition(x, w, b), rtol=2e-6,
                               atol=2e-6)
    rec = pk.causal_conv_log()[-1]
    assert (rec["path"], rec["reason"]) == ("kernel", None)
    after = pk.traced_call_sums()
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 0, "flash_operands_in_place": 0,
        "flash_operands_copied": 0, "ssd_calls": 0, "ssd_calls_composed": 0,
        "mla_expand_calls": 0, "mla_expand_calls_composed": 0,
        "moe_run_sum_calls": 0, "moe_run_sum_calls_composed": 0,
        "causal_conv_calls": 1, "causal_conv_calls_composed": 0,
        "gated_norm_calls": 0, "gated_norm_calls_composed": 0,
        "cca_mix_calls": 0, "cca_mix_calls_composed": 0}


@pytest.mark.parametrize("case", ["channels_96", "nine_taps",
                                  "rows_off_the_tiles",
                                  "start_off_the_tiles"])
def test_on_the_tpu_a_refused_shape_takes_the_composition_aloud(on_tpu,
                                                                 case):
    t, channels, taps, dtype, start = REFUSED[case]
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((1, t, start + channels)), dtype)
    w = jnp.asarray(r.standard_normal((channels, taps)), dtype)
    before = pk.traced_call_sums()
    with pytest.warns(pk.KernelFallbackWarning,
                      match="causal_conv.*supports"):
        got = raw("causal_conv1d")(x, w, activation="silu", start=start)
    np.testing.assert_array_equal(
        _f32(got), _f32(_causal_conv1d_silu(x, w, None, start)))
    rec = pk.causal_conv_log()[-1]
    assert rec["path"] == "composition" and "supports" in rec["reason"]
    assert _sums_moved(before) == {"causal_conv_calls": 1,
                                   "causal_conv_calls_composed": 1}


def test_under_a_gspmd_mesh_the_composition_runs_aloud(on_tpu):
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh

    (x, w, b), _ = _operands("one_block", jnp.float32)
    with use_mesh(build_mesh(dp=2, devices=jax.devices()[:2])):
        with pytest.warns(pk.KernelFallbackWarning, match="GSPMD"):
            got = pk.causal_conv1d(x, w, b)
    np.testing.assert_array_equal(got, _causal_conv1d_silu(x, w, b))
    assert pk.causal_conv_log()[-1]["reason"].startswith(pk.GSPMD_REASON)


def test_the_compiled_steps_account_counts_its_convolutions():
    """``TrainStep.compile_account()`` over a ``nemotron_h_tiny`` of two
    Mamba blocks: their convolutions (x, B and C, each where it lies in the
    projection's result) traced while the step compiled, all the
    composition's off the TPU."""
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
    assert (account["causal_conv_calls"],
            account["causal_conv_calls_composed"]) == (6, 6)
    # z 64 | x 64 | B 32 | C 32 | dt 8
    assert [(r["shapes"], r["start"], r["path"])
            for r in pk.causal_conv_log()[-3:]] == [
        (((1, 32, 200), (64, 4)), 64, "composition"),
        (((1, 32, 200), (32, 4)), 128, "composition"),
        (((1, 32, 200), (32, 4)), 160, "composition")]
