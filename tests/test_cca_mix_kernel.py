"""The ``cca_mix_fwd`` / ``cca_mix_bwd`` kernels
(``ops/pallas/cca_mix_kernel.py``) in interpret mode on the CPU against the
XLA composition ``models/zaya.py _mix_composed``: q^, k^, v' and the
gradients of q~, k~, v, the four tap tensors and ``tau``, over more than one
row block so that the halo and the backward's carried rows are crossed;
causality; what the dispatcher ``ops.pallas.cca_mix`` takes where, what it
records, and what a compiled step's account says of it; one
``CompressedConvAttention`` layer through the kernels against the
benchmark's reference."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import zaya
from paddle_tpu.models.laguna import rope_tables
from paddle_tpu.models.zaya import _mix_composed
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import cca_mix_kernel as ck

EPS = 1e-5
kernel = functools.partial(ck.cca_mix_pallas, epsilon=EPS, interpret=True)
composition = jax.jit(functools.partial(_mix_composed, eps=EPS))
OUTS = ("q^", "k^", "v'")
NAMES = ("q~", "k~", "v", "q_conv0", "q_conv1", "k_conv0", "k_conv1", "tau")

# (T, n, kv, D, taps, rotary share, block): the published make (groups of
# four over two kv heads, two taps each, half a head rotated) over four row
# blocks of two chunks; a kv head a q head, three and four taps, three blocks
# of one chunk; four kv heads (two of them shift) under a full rotation; a
# head of two tiles, a quarter of it rotated
CASES = {
    "published_make": (128, 8, 2, 128, (2, 2), 0.5, (32, 16)),
    "ungrouped_3_4_taps": (96, 2, 2, 128, (3, 4), 0.25, (32, 32)),
    "four_kv_full_rotary": (64, 8, 4, 128, (2, 2), 1.0, (32, 16)),
    "head_of_256": (48, 2, 2, 256, (2, 3), 0.25, (16, 16)),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# values: in float32 the kernels' are the composition's but for the order of
# a sum (the statistics over 256 lanes, a dot's); in bfloat16 both sides round
# where the other does, so a value moves by a unit in the last of 8 bits
# only where that order moved a sum across a rounding boundary
VALUE_TOL = {"float32": 4e-6, "bfloat16": 1.6e-2}
# gradients, of each operand's largest entry: float32 to the order of
# summation; in bfloat16 the kernels round each of q~'s and k~'s gradients
# ONCE (the composition rounds the convolutions' path and the mean's path
# each, then their sum), and the taps' sums once
GRAD_TOL = {"float32": 2e-5, "bfloat16": 1.6e-2}


def _operands(case, dtype, batch=1, seed=0):
    seq, n, kv, d, taps, share, block = CASES[case]
    r = np.random.default_rng(seed + seq + n)

    def draw(*shape, scale=1.0):
        return jnp.asarray(scale * r.standard_normal(shape), dtype)

    cos, sin, _ = rope_tables(d, seq, {"rope_theta": 10000.0,
                                       "partial_rotary_factor": share})
    return (draw(batch, seq, n, d), draw(batch, seq, kv, d),
            draw(batch, seq, kv, d),
            draw(n * d, taps[0], scale=taps[0] ** -0.5),
            draw(n, taps[1], d, d, scale=(taps[1] * d) ** -0.5),
            draw(kv * d, taps[0], scale=taps[0] ** -0.5),
            draw(kv, taps[1], d, d, scale=(taps[1] * d) ** -0.5),
            jnp.asarray(1 + 0.2 * r.standard_normal((kv,)), jnp.float32),
            jnp.asarray(cos), jnp.asarray(sin)), block


@functools.lru_cache(maxsize=None)
def _kernel_and_composition(case, dtype, batch):
    """``(outputs, gradients)`` of the kernels and of the composition on a
    case's operands under one random cotangent, computed once for the two
    tests that read them."""
    ops, block = _operands(case, DTYPES[dtype], batch)
    pairs = [jax.vjp(fn, *ops)
             for fn in (functools.partial(kernel, block=block), composition)]
    r = np.random.default_rng(5)
    co = tuple(jnp.asarray(r.standard_normal(o.shape), o.dtype)
               for o in pairs[0][0])
    return ops, [(value, vjp(co)[:len(NAMES)]) for value, vjp in pairs]


def _f32(a):
    return np.asarray(a, np.float32)


SWEEP = [(c, d, 1) for c in CASES for d in DTYPES] \
    + [("published_make", d, 2) for d in DTYPES]
IDS = [f"{c}-{d}-{b}row" for c, d, b in SWEEP]


@pytest.mark.parametrize("case,dtype,batch", SWEEP, ids=IDS)
def test_kernels_match_the_composition(case, dtype, batch):
    ops, ((got, _), (want, _)) = _kernel_and_composition(case, dtype, batch)
    tol = VALUE_TOL[dtype]
    for name, a, b, like in zip(OUTS, got, want, ops):
        assert a.shape == like.shape and a.dtype == like.dtype, name
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=tol, atol=tol,
                                   err_msg=name)
    # the values pass through untouched, a row later for half the heads
    np.testing.assert_array_equal(_f32(got[2]), _f32(want[2]))


@pytest.mark.parametrize("case,dtype,batch", SWEEP, ids=IDS)
def test_gradients_of_every_operand_match_the_compositions(case, dtype,
                                                           batch):
    """The hand-written backward against ``jax.grad`` of the composition,
    under one random cotangent a result."""
    _, ((_, got), (_, want)) = _kernel_and_composition(case, dtype, batch)
    tol = GRAD_TOL[dtype]
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            _f32(a), _f32(b), rtol=tol,
            atol=tol * float(np.max(np.abs(_f32(b)))), err_msg=name)


def test_a_change_at_position_t_reaches_no_output_before_t():
    """Rows ``t`` on of q~, k~ and v changed: every result before ``t`` is
    what it was, bit for bit, across the block boundary at 32 and the chunk
    boundary at 48; q^ and k^ move at ``t`` itself, the shifted value head
    one row later."""
    ops, block = _operands("published_make", jnp.float32)
    fn = functools.partial(kernel, block=block)
    base = fn(*ops)
    for t in (31, 32, 47, 100):
        moved = fn(*(x.at[:, t:].add(1.0) for x in ops[:3]), *ops[3:])
        for name, a, b in zip(OUTS, moved, base):
            np.testing.assert_array_equal(a[:, :t], b[:, :t], err_msg=name)
            assert float(jnp.abs(a[:, t] - b[:, t]).max()) > 1e-3, name
        assert float(jnp.abs(moved[2][:, t, 1] - base[2][:, t, 1]).max()) \
            == 0.0
        assert float(jnp.abs(moved[2][:, t + 1, 1]
                             - base[2][:, t + 1, 1]).max()) > 0.5


def test_a_row_s_first_positions_read_zero_and_not_the_row_before():
    """Two batch rows: the second's results are what it gives alone (the
    rows before a row's first read zero, not the first row's last), and the
    shifted value head's first position is zero."""
    ops, block = _operands("published_make", jnp.float32, batch=2)
    fn = functools.partial(kernel, block=block)
    both = fn(*ops)
    alone = fn(*(x[1:] for x in ops[:3]), *ops[3:])
    for name, a, b in zip(OUTS, both, alone):
        np.testing.assert_array_equal(a[1:], b, err_msg=name)
    np.testing.assert_array_equal(both[2][:, 0, 1], 0.0)
    np.testing.assert_array_equal(both[2][:, 1:, 1], ops[2][:, :-1, 1])
    np.testing.assert_array_equal(both[2][:, :, 0], ops[2][:, :, 0])


def test_the_gradients_come_back_in_their_operands_dtypes():
    ops, block = _operands("published_make", jnp.bfloat16)
    grads = jax.grad(lambda *o: sum(
        jnp.sum(x.astype(jnp.float32)) for x in kernel(*o, block=block)),
        argnums=tuple(range(8)))(*ops)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 7 + [jnp.float32]
    assert [g.shape for g in grads] == [o.shape for o in ops[:8]]


# ---------------------------------------------------------- the dispatcher --

# (T, n, kv, D, taps, rot, dtype)
REFUSED = {
    "odd_kv_heads": (64, 3, 3, 128, (2, 2), 64, jnp.bfloat16),
    "head_of_64": (64, 4, 2, 64, (2, 2), 32, jnp.bfloat16),
    "ragged_rows": (40, 4, 2, 128, (2, 2), 64, jnp.float32),
    "q_heads_off_the_groups": (64, 5, 2, 128, (2, 2), 64, jnp.float32),
    "taps_beyond_the_halo": (64, 4, 2, 128, (8, 12), 64, jnp.float32),
    "int8": (64, 4, 2, 128, (2, 2), 64, jnp.int8),
}


def _refused_operands(case):
    seq, n, kv, d, taps, rot, dtype = REFUSED[case]
    r = np.random.default_rng(2)

    def draw(*shape):
        return jnp.asarray(0.3 * r.standard_normal(shape)).astype(dtype)

    table = jnp.asarray(r.uniform(size=(seq, rot // 2)), jnp.float32)
    return (draw(1, seq, n, d), draw(1, seq, kv, d), draw(1, seq, kv, d),
            draw(n * d, taps[0]), draw(n, taps[1], d, d),
            draw(kv * d, taps[0]), draw(kv, taps[1], d, d),
            jnp.ones((kv,), jnp.float32), table, 1 - table)


@pytest.mark.parametrize("case", list(REFUSED))
def test_supports_refuses_what_the_kernels_cannot_tile(case):
    assert not ck.supports(*REFUSED[case])
    with pytest.raises(ValueError, match="supports"):
        ck.cca_mix_pallas(*_refused_operands(case), epsilon=EPS)


def test_supports_takes_the_published_shape():
    # [1, 16384, 8 | 2, 128], two taps each, 64 dims rotated
    assert ck.supports(16384, 8, 2, 128, (2, 2), 64, jnp.bfloat16)
    assert ck._pick_block(16384, jnp.bfloat16) == (256, 256)
    assert ck._pick_block(16384, jnp.float32) == (128, 128)
    # heads of two tiles, a whole head rotated; nothing rotated is another
    # attention (no table to read)
    assert ck.supports(4096, 16, 4, 256, (4, 4), 256, jnp.float32)
    assert not ck.supports(4096, 16, 4, 256, (4, 4), 0, jnp.float32)
    # the tiny test config: heads of 16
    assert not ck.supports(48, 4, 2, 16, (2, 2), 8, jnp.float32)


def test_operands_that_do_not_go_together_are_refused():
    ops, _ = _operands("published_make", jnp.float32)
    q, k, v, qw0, qw1, kw0, kw1, tau, cos, sin = ops
    for bad in ((q, k[:, :64], v), (q, k, v.astype(jnp.bfloat16)),
                (q, k, v, qw0[:512]), (q, k, v, qw0, qw1[:, :1]),
                (q, k, v, qw0, qw1, kw0, kw1.astype(jnp.bfloat16)),
                (q, k, v, qw0, qw1, kw0, kw1, tau[:1]),
                (q, k, v, qw0, qw1, kw0, kw1, tau, cos[:64])):
        with pytest.raises(ValueError, match="supports"):
            kernel(*bad, *ops[len(bad):])


def test_a_block_that_does_not_tile_is_refused():
    ops, _ = _operands("published_make", jnp.float32)
    for block in ((48, 16), (32, 8), (64, 24)):
        with pytest.raises(ValueError, match="does not tile"):
            kernel(*ops, block=block)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatcher sees on the chip: kernels on, the backend's name
    ``tpu``, and the kernels themselves in interpret mode."""
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ck, "cca_mix_pallas", functools.partial(
        ck.cca_mix_pallas, interpret=True))


def _same_function(got, want):
    """The composition run twice (the eager ops' second sighting is a
    compiled one: a fused sum here and there)."""
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        tol = 4e-6 if a.dtype == jnp.float32 else 1.6e-2
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=tol, atol=tol)


def _sums_moved(before):
    after = pk.traced_call_sums()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def test_off_the_tpu_the_composition_runs_without_a_word():
    ops, _ = _operands("published_make", jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = pk.cca_mix(*ops, EPS)
    _same_function(got, _mix_composed(*ops, EPS))
    rec = pk.cca_mix_log()[-1]
    assert rec["path"] == "composition" and "no TPU" in rec["reason"]
    assert rec["shapes"] == ((1, 128, 8, 128), (1, 128, 2, 128))
    assert _sums_moved(before) == {"cca_mix_calls": 1,
                                   "cca_mix_calls_composed": 1}


def test_on_the_tpu_the_kernels_run_and_are_recorded(on_tpu):
    ops, _ = _operands("published_make", jnp.float32)
    before = pk.traced_call_sums()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = pk.cca_mix(*ops, EPS)
    for a, b in zip(got, composition(*ops)):
        np.testing.assert_allclose(a, b, rtol=4e-6, atol=4e-6)
    rec = pk.cca_mix_log()[-1]
    assert (rec["path"], rec["reason"]) == ("kernel", None)
    after = pk.traced_call_sums()
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 0, "flash_operands_in_place": 0,
        "flash_operands_copied": 0, "ssd_calls": 0, "ssd_calls_composed": 0,
        "mla_expand_calls": 0, "mla_expand_calls_composed": 0,
        "moe_run_sum_calls": 0, "moe_run_sum_calls_composed": 0,
        "causal_conv_calls": 0, "causal_conv_calls_composed": 0,
        "gated_norm_calls": 0, "gated_norm_calls_composed": 0,
        "cca_mix_calls": 1, "cca_mix_calls_composed": 0}


@pytest.mark.parametrize("case", ["odd_kv_heads", "head_of_64",
                                  "ragged_rows"])
def test_on_the_tpu_a_refused_shape_takes_the_composition_aloud(on_tpu,
                                                                 case):
    ops = _refused_operands(case)
    before = pk.traced_call_sums()
    with pytest.warns(pk.KernelFallbackWarning, match="cca_mix.*supports"):
        got = pk.cca_mix(*ops, EPS)
    _same_function(got, _mix_composed(*ops, EPS))
    rec = pk.cca_mix_log()[-1]
    assert rec["path"] == "composition" and "supports" in rec["reason"]
    assert _sums_moved(before) == {"cca_mix_calls": 1,
                                   "cca_mix_calls_composed": 1}


def test_taps_of_another_dtype_take_the_composition_aloud(on_tpu):
    """Float32 tap matrices beside bfloat16 latents (a model that was not
    decorated as a whole): the kernels' dots take both operands in the
    stored dtype, so the composition serves."""
    ops, _ = _operands("published_make", jnp.bfloat16)
    ops = ops[:4] + (ops[4].astype(jnp.float32),) + ops[5:]
    with pytest.warns(pk.KernelFallbackWarning,
                      match="float32 beside q bfloat16"):
        got = pk.cca_mix(*ops, EPS)
    assert got[0].dtype == jnp.bfloat16
    assert pk.cca_mix_log()[-1]["path"] == "composition"


def test_under_a_gspmd_mesh_the_composition_runs_aloud(on_tpu):
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh

    ops, _ = _operands("published_make", jnp.float32)
    with use_mesh(build_mesh(dp=2, devices=jax.devices()[:2])):
        with pytest.warns(pk.KernelFallbackWarning, match="GSPMD"):
            got = pk.cca_mix(*ops, EPS)
    _same_function(got, _mix_composed(*ops, EPS))
    assert pk.cca_mix_log()[-1]["reason"].startswith(pk.GSPMD_REASON)


def test_the_compiled_steps_account_counts_its_mixes():
    """``TrainStep.compile_account()`` over ``zaya_tiny``: one mix a layer
    traced while the step compiled (``jax.checkpoint`` replays the trace for
    the recomputation, and the backward is the op's own rule), all the
    composition's off the TPU and at heads of 16."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = zaya.zaya_tiny(num_hidden_layers=2)
    step = TrainStep(
        model, lambda logits, labels: model.loss(logits, labels),
        paddle.optimizer.AdamW(learning_rate=1e-3,
                               parameters=model.parameters()),
        remat=["flash_attention_out", "flash_attention_lse"])
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 512, (1, 32)).astype(np.int32))
    step(ids, ids)
    account = step.compile_account()
    assert (account["cca_mix_calls"],
            account["cca_mix_calls_composed"]) == (2, 2)
    assert [(r["shapes"], r["path"]) for r in pk.cca_mix_log()[-2:]] == [
        (((1, 32, 4, 16), (1, 32, 2, 16)), "composition")] * 2


# ------------------------------------------------- a layer through the kernels

@pytest.mark.parametrize("through", ["kernels", "composition"])
def test_a_layer_through_the_kernels_matches_the_reference(monkeypatch,
                                                           through):
    """One ``CompressedConvAttention`` of four q heads over two kv heads of
    128 in a hidden size of 256, a row of 64 in two row blocks, against the
    benchmark's reference (``chipbench/reference/zaya.py cca``, what
    ``tests/test_zaya_pieces.py`` holds the tiny layer to): through the
    kernels (interpret mode) as through the composition."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    from chipbench.reference import zaya as ref

    if through == "kernels":
        monkeypatch.setattr(
            pk, "cca_mix", lambda *o: ck.cca_mix_pallas(
                *o[:-1], epsilon=o[-1], interpret=True, block=(32, 16)))
    paddle.seed(3)
    rope = {"rope_theta": 5000000, "partial_rotary_factor": 0.5}
    attn = zaya.CompressedConvAttention(256, 4, 2, 128, (2, 2), rope, 0.05,
                                        0.05, EPS)
    attn.temperature.set_value(np.array([0.7, 1.4], np.float32))
    cfg = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
               head_dim=128, cca_time0=2, cca_time1=2, router_hidden_size=0,
               moe_intermediate_size=0, num_experts=0, num_hidden_layers=1,
               vocab_size=0, rms_norm_eps=EPS, **rope)
    p = {"attn." + n: a._data for n, a in attn.named_parameters()}
    h = jnp.asarray(np.random.RandomState(9).randn(1, 64, 256), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = attn(Tensor(h))._data[0]
        want = ref.cca(h[0], p, cfg)
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(want).max()))
