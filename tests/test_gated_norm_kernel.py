"""The ``gated_norm_fwd`` / ``gated_norm_bwd`` kernels
(``ops/pallas/gated_norm_kernel.py``) in interpret mode on the CPU against
the XLA composition ``models/nemotron_h.py _gated_norm_composed``, values
and the gradients of y, z and the gain; the gate read where it lies in a
wider operand; what the dispatcher ``ops.pallas.gated_rms_norm`` takes
where, what it records, and what a compiled step's account says of it."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.nemotron_h import _gated_norm_composed
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import gated_norm_kernel as gk
from paddle_tpu.ops.registry import raw

EPS = 1e-5
kernel = functools.partial(gk.gated_norm_pallas, epsilon=EPS, interpret=True)
composition = jax.jit(functools.partial(_gated_norm_composed, epsilon=EPS),
                      static_argnames=("groups", "start"))
NAMES = ("y", "z", "weight")

# groups -> (channels, block): the whole width one group of two tiles;
# two groups of a tile each; the published eight, several row blocks of
# several chunks each
GROUPS = {1: (256, None), 2: (256, (16, 16)), 8: (1024, (32, 16))}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# values: float32 differs by the order the compiler gives a group's sum of
# squares; in bfloat16 both sides round ONCE, the result, so a value moves
# by a unit in the last of 8 bits only where that order moved it across a
# rounding boundary
VALUE_TOL = {"float32": 2e-6, "bfloat16": 8e-3}
# gradients, of each operand's largest entry: the scan kernels' 1e-4 in
# float32; in bfloat16 the one rounding of each gradient
GRAD_TOL = {"float32": 1e-4, "bfloat16": 8e-3}


def _operands(groups, dtype, batch=1, rows=64, wide=0, seed=0):
    channels, block = GROUPS[groups]
    r = np.random.default_rng(seed + groups + batch)
    y = jnp.asarray(r.standard_normal((batch, rows, channels)), dtype)
    z = jnp.asarray(r.standard_normal((batch, rows, channels + wide)), dtype)
    w = jnp.asarray(1.0 + 0.2 * r.standard_normal((channels,)), dtype)
    return (y, z, w), block


@functools.lru_cache(maxsize=None)
def _kernel_and_composition(groups, dtype, batch):
    """``(out, gradients)`` of the kernels and of the composition on a
    case's operands under one random cotangent, computed once for the two
    tests that read them."""
    ops, block = _operands(groups, DTYPES[dtype], batch)
    co = jnp.asarray(np.random.default_rng(5).standard_normal(ops[0].shape),
                     ops[0].dtype)
    out = []
    for fn in (functools.partial(kernel, groups=groups, block=block),
               functools.partial(composition, groups=groups)):
        value, vjp = jax.vjp(fn, *ops)
        out.append((value, vjp(co)))
    return ops, out


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("batch", [1, 2], ids=["one_row", "two_rows"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("groups", list(GROUPS))
def test_kernels_match_the_composition(groups, dtype, batch):
    ops, ((got, _), (want, _)) = _kernel_and_composition(groups, dtype, batch)
    assert got.shape == ops[0].shape and got.dtype == ops[0].dtype
    tol = VALUE_TOL[dtype]
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("batch", [1, 2], ids=["one_row", "two_rows"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("groups", list(GROUPS))
def test_gradients_of_every_operand_match_the_compositions(groups, dtype,
                                                           batch):
    """The hand-written backward against ``jax.grad`` of the composition,
    under one random cotangent."""
    _, ((_, got), (_, want)) = _kernel_and_composition(groups, dtype, batch)
    tol = GRAD_TOL[dtype]
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            _f32(a), _f32(b), rtol=tol,
            atol=tol * float(np.max(np.abs(_f32(b)))), err_msg=name)


def test_a_group_s_statistics_are_its_own_lanes():
    """A row's second group scaled a thousandfold leaves its first group's
    values as they were, bit for bit, and the scaled group's too but for
    what ``epsilon`` weighs beside a mean square of 0.3 (the norm divides
    the scale out again)."""
    (y, z, w), block = _operands(2, jnp.float32)
    fn = functools.partial(kernel, groups=2, block=block)
    got, scaled = fn(y, z, w), fn(y.at[..., 128:].multiply(1024.0), z, w)
    np.testing.assert_array_equal(scaled[..., :128], got[..., :128])
    np.testing.assert_allclose(scaled[..., 128:], got[..., 128:], rtol=1e-4)
    # and one group over the whole width is another function
    assert float(jnp.max(jnp.abs(
        kernel(y, z, w, groups=1) - got))) > 1e-2


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_the_gate_is_read_where_it_lies_in_a_wider_operand(dtype):
    """``start=``: the norm under lanes ``start .. start + C`` of a wider z
    is the norm under that slice, bit for bit, and z's gradient is the
    slice's padded with zeros (two groups in, one group's width before
    them and half a one behind)."""
    (y, wide, w), block = _operands(2, DTYPES[dtype], batch=2, wide=192)
    co = jnp.asarray(np.random.default_rng(5).standard_normal(y.shape),
                     y.dtype)
    fn = functools.partial(kernel, groups=2, block=block)
    out, vjp = jax.vjp(functools.partial(fn, start=128), y, wide, w)
    want, want_vjp = jax.vjp(fn, y, wide[..., 128:384], w)
    np.testing.assert_array_equal(_f32(out), _f32(want))
    (dy, dz, dw), (want_dy, want_dz, want_dw) = vjp(co), want_vjp(co)
    assert dz.shape == wide.shape and dz.dtype == wide.dtype
    np.testing.assert_array_equal(_f32(dz[..., 128:384]), _f32(want_dz))
    np.testing.assert_array_equal(_f32(dz[..., :128]), 0.0)
    np.testing.assert_array_equal(_f32(dz[..., 384:]), 0.0)
    np.testing.assert_array_equal(_f32(dy), _f32(want_dy))
    np.testing.assert_array_equal(_f32(dw), _f32(want_dw))
    composed = composition(y, wide, w, groups=2, start=128)
    np.testing.assert_allclose(_f32(out), _f32(composed),
                               rtol=VALUE_TOL[dtype], atol=VALUE_TOL[dtype])


def test_bfloat16_operands_keep_float32_sums_and_round_once():
    """A row of 255 ones and one 16: the sum of squares is 255 + 256 = 511
    (a bfloat16 running sum of ones stops at 256), so the ones come out as
    ``sqrt(256 / 511)`` = 0.70779, rounded ONCE to 0.70703; the output and
    every gradient come back in the operands' dtype."""
    bf16 = jnp.bfloat16
    y = jnp.ones((1, 16, 256), bf16).at[..., 0].set(16.0)
    z = jnp.full((1, 16, 256), 30.0, bf16)     # silu(30) = 30 to 1e-12
    w = jnp.ones((256,), bf16)
    got = kernel(y, z, w, groups=1)
    assert got.dtype == bf16
    lossy = functools.reduce(lambda a, v: a + v,
                             [jnp.ones((), bf16)] * 300)
    assert float(lossy) == 256.0
    np.testing.assert_array_equal(
        _f32(got[..., 1:]), _f32(jnp.asarray((256 / 511) ** 0.5, bf16)))
    np.testing.assert_array_equal(_f32(got),
                                  _f32(composition(y, z, w, groups=1)))
    (y, z, w), block = _operands(2, bf16)
    grads = jax.grad(lambda *o: jnp.sum(kernel(
        *o, groups=2, block=block).astype(jnp.float32)),
        argnums=(0, 1, 2))(y, z, w)
    assert [g.dtype for g in grads] == [bf16] * 3


# ---------------------------------------------------------- the dispatcher --

# (rows, channels, groups, dtype, start)
REFUSED = {
    "group_width_96": (64, 192, 2, jnp.bfloat16, 0),
    "groups_that_do_not_divide": (64, 256, 3, jnp.bfloat16, 0),
    "int8": (64, 256, 2, jnp.int8, 0),
    "ragged_rows": (24, 256, 2, jnp.float32, 0),
    "start_off_a_block": (64, 256, 1, jnp.float32, 128),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_supports_refuses_what_the_kernels_cannot_tile(case):
    assert not gk.supports(*REFUSED[case])
    rows, channels, groups, dtype, start = REFUSED[case]
    with pytest.raises(ValueError, match="supports"):
        gk.gated_norm_pallas(
            jnp.ones((1, rows, channels), dtype),
            jnp.ones((1, rows, start + channels), dtype),
            jnp.ones((channels,), dtype), groups=groups, epsilon=EPS,
            start=start)


def test_supports_takes_the_published_shape():
    # y [4096, 8192] over 8 groups, the gate the first 8,192 lanes of the
    # projection's [4096, 18560]
    assert gk.supports(4096, 8192, 8, jnp.bfloat16, 0)
    assert gk._pick_block(4096, 1024, jnp.bfloat16) == (512, 64)
    assert gk._pick_block(4096, 1024, jnp.float32) == (256, 64)
    # a gate that lies behind other lanes, whole groups' widths in
    assert gk.supports(4096, 8192, 8, jnp.bfloat16, 10240)
    assert not gk.supports(4096, 8192, 8, jnp.bfloat16, 10368)
    # a width no block of 16 rows holds in a MiB
    assert not gk.supports(4096, 32768, 1, jnp.float32)
    # the tiny test config's norm: 8 * 8 lanes over 2 groups
    assert not gk.supports(64, 64, 2, jnp.float32)


def test_operands_that_do_not_go_together_are_refused():
    y, w = jnp.ones((1, 64, 256)), jnp.ones((256,))
    for z, weight, start in (
            (jnp.ones((1, 64, 384)), w, 256),       # lanes beyond z
            (jnp.ones((1, 32, 256)), w, 0),         # other rows
            (jnp.ones((1, 64, 256), jnp.bfloat16), w, 0),
            (jnp.ones((1, 64, 256)), jnp.ones((128,)), 0)):
        with pytest.raises(ValueError, match="supports"):
            kernel(y, z, weight, groups=2, start=start)


def test_a_block_that_does_not_tile_is_refused():
    (y, z, w), _ = _operands(2, jnp.float32)
    for block in ((48, 16), (32, 8), (64, 24)):
        with pytest.raises(ValueError, match="does not tile"):
            kernel(y, z, w, groups=2, block=block)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatcher sees on the chip: kernels on, the backend's name
    ``tpu``, and the kernels themselves in interpret mode."""
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gk, "gated_norm_pallas", functools.partial(
        gk.gated_norm_pallas, interpret=True))


def _sums_moved(before):
    after = pk.traced_call_sums()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _op(y, z, w, groups=2, start=0):
    return raw("mamba_gated_rms_norm")(y, z, w, groups=groups, epsilon=EPS,
                                       start=start)


def test_off_the_tpu_the_composition_runs_without_a_word():
    (y, z, w), _ = _operands(2, jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = _op(y, z, w)
    np.testing.assert_array_equal(got, _gated_norm_composed(y, z, w, 2, EPS))
    rec = pk.gated_norm_log()[-1]
    assert rec["path"] == "composition" and "no TPU" in rec["reason"]
    assert (rec["shapes"], rec["groups"], rec["start"]) \
        == (((1, 64, 256), (1, 64, 256)), 2, 0)
    assert _sums_moved(before) == {"gated_norm_calls": 1,
                                   "gated_norm_calls_composed": 1}


def test_on_the_tpu_the_kernels_run_and_are_recorded(on_tpu):
    (y, z, w), _ = _operands(2, jnp.float32, wide=128)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = _op(y, z, w, start=128)
    np.testing.assert_allclose(got, composition(y, z, w, groups=2, start=128),
                               rtol=2e-6, atol=2e-6)
    rec = pk.gated_norm_log()[-1]
    assert (rec["path"], rec["reason"], rec["start"]) == ("kernel", None, 128)
    after = pk.traced_call_sums()
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 0, "flash_operands_in_place": 0,
        "flash_operands_copied": 0, "ssd_calls": 0, "ssd_calls_composed": 0,
        "mla_expand_calls": 0, "mla_expand_calls_composed": 0,
        "moe_run_sum_calls": 0, "moe_run_sum_calls_composed": 0,
        "causal_conv_calls": 0, "causal_conv_calls_composed": 0,
        "gated_norm_calls": 1, "gated_norm_calls_composed": 0,
        "cca_mix_calls": 0, "cca_mix_calls_composed": 0}


@pytest.mark.parametrize("case", ["group_width_96", "ragged_rows",
                                  "start_off_a_block"])
def test_on_the_tpu_a_refused_shape_takes_the_composition_aloud(on_tpu,
                                                                 case):
    rows, channels, groups, dtype, start = REFUSED[case]
    r = np.random.default_rng(2)
    y = jnp.asarray(r.standard_normal((1, rows, channels)), dtype)
    z = jnp.asarray(r.standard_normal((1, rows, start + channels)), dtype)
    w = jnp.ones((channels,), dtype)
    before = pk.traced_call_sums()
    with pytest.warns(pk.KernelFallbackWarning,
                      match="gated_norm.*supports"):
        got = _op(y, z, w, groups=groups, start=start)
    np.testing.assert_array_equal(
        _f32(got), _f32(_gated_norm_composed(y, z, w, groups, EPS, start)))
    rec = pk.gated_norm_log()[-1]
    assert rec["path"] == "composition" and "supports" in rec["reason"]
    assert _sums_moved(before) == {"gated_norm_calls": 1,
                                   "gated_norm_calls_composed": 1}


def test_a_gate_of_another_dtype_takes_the_composition_aloud(on_tpu):
    (y, z, w), _ = _operands(2, jnp.float32)
    with pytest.warns(pk.KernelFallbackWarning, match="bfloat16 beside y"):
        got = pk.gated_rms_norm(y, z.astype(jnp.bfloat16), w, 2, EPS)
    assert got.dtype == y.dtype
    assert pk.gated_norm_log()[-1]["path"] == "composition"


def test_under_a_gspmd_mesh_the_composition_runs_aloud(on_tpu):
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh

    (y, z, w), _ = _operands(2, jnp.float32)
    with use_mesh(build_mesh(dp=2, devices=jax.devices()[:2])):
        with pytest.warns(pk.KernelFallbackWarning, match="GSPMD"):
            got = pk.gated_rms_norm(y, z, w, 2, EPS)
    np.testing.assert_array_equal(got, _gated_norm_composed(y, z, w, 2, EPS))
    assert pk.gated_norm_log()[-1]["reason"].startswith(pk.GSPMD_REASON)


def test_the_compiled_steps_account_counts_its_gated_norms():
    """``TrainStep.compile_account()`` over a ``nemotron_h_tiny`` of two
    Mamba blocks: one gated norm a mixer traced while the step compiled, the
    gate read where it lies in the projection's result, all the
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
    assert (account["gated_norm_calls"],
            account["gated_norm_calls_composed"]) == (2, 2)
    # another family's op: none in this step
    assert (account["cca_mix_calls"],
            account["cca_mix_calls_composed"]) == (0, 0)
    # y 64 under z 64 | x 64 | B 32 | C 32 | dt 8
    assert [(r["shapes"], r["groups"], r["start"], r["path"])
            for r in pk.gated_norm_log()[-2:]] == [
        (((1, 32, 64), (1, 32, 200)), 2, 0, "composition")] * 2
