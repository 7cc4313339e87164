"""The flash kernels' HBM interface (``ops/pallas/attention_kernel.py``):
where a call's kv heads are grouped, an operand whose head width is a whole
number of 128 lanes is read and written where XLA holds it, ``[batch, seq,
heads * width]`` with the head picked by the block index maps; any other
operand crosses as the copy ``[batch * heads, seq, width]``; the row
statistics cross lane-dense in every call.

In interpret mode on the CPU: values and all three gradients against
``_xla_attention`` for each form the index maps take, and what the
dispatcher's record and the compiled step's account say of a call.  Lowered
for the TPU: no transpose of an in-place operand stands beside the custom
calls.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import attention_kernel as ak

SEQ, BLOCK = 256, 64


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.fixture
def small_blocks(monkeypatch):
    """64 x 64 blocks, so that a 256-token sequence has four."""
    monkeypatch.setattr(ak, "_blocks", lambda seq_q, seq_k: (BLOCK, BLOCK))


def _out_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do)


# (batch, q heads, kv heads, q/k width, v width, window): what the index
# maps have to get right, one case each
CASES = {
    "plain": (1, 4, 4, 128, 128, None),             # not grouped: copies
    "grouped_6_over_2": (1, 6, 2, 128, 128, None),
    "window": (1, 6, 2, 128, 128, 100),
    "mixed_192_over_128": (1, 4, 2, 192, 128, None),
    "batch_3": (3, 6, 2, 128, 128, None),           # bn -> (b, n)
    "batch_2_mixed_window": (2, 4, 2, 192, 128, 70),
    "two_tiles_wide": (2, 2, 1, 256, 256, None),    # a head is two tiles
    "copied_64": (2, 4, 2, 64, 64, None),
    "kanana_like": (2, 4, 4, 192, 128, None),       # not grouped: copies
}


def _operands(case, dtype=jnp.float32):
    b, n, nkv, d, dv, window = CASES[case]
    return (_rand((b, SEQ, n, d), 1, dtype), _rand((b, SEQ, nkv, d), 2, dtype),
            _rand((b, SEQ, nkv, dv), 3, dtype),
            _rand((b, SEQ, n, dv), 4, dtype), window)


@pytest.mark.parametrize("case", list(CASES))
def test_values_and_gradients_in_either_layout(small_blocks, case):
    q, k, v, do, window = _operands(case)
    got = _out_and_grads(
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=True, interpret=True, window=window),
        q, k, v, do)
    want = _out_and_grads(
        lambda q, k, v: pk._xla_attention(q, k, v, is_causal=True,
                                          window=window), q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-5,
                                   atol=5e-5, err_msg=name)


@pytest.mark.parametrize("case", ["two_tiles_wide", "grouped_6_over_2",
                                  "window", "mixed_192_over_128", "batch_3"])
def test_in_place_and_copied_operands_give_the_same_numbers(
        small_blocks, monkeypatch, case):
    """Bit for bit: the layout changes where a block comes from, never a
    value (one kernel, two index maps)."""
    q, k, v, do, window = _operands(case, jnp.bfloat16)
    run = lambda: _out_and_grads(                           # noqa: E731
        lambda q, k, v: ak.flash_attention_pallas(
            q, k, v, is_causal=True, interpret=True, window=window),
        q, k, v, do)
    in_place = run()
    assert ak.operand_layouts(*CASES[case][3:5], group=2)[0]
    monkeypatch.setattr(ak, "_in_place", lambda width, group: False)
    for a, b in zip(run(), in_place):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_kernel_layouts_and_the_statistics_rows(small_blocks):
    """What crosses: ``[batch, seq, heads * width]`` where the width is a
    whole number of tiles, the copy where it is not, O in v's layout, and
    the log-sum-exp as ``[batch * heads, seq / block, block]``: the value
    of every row, against the dense scores."""
    q, k, v, _, _ = _operands("batch_2_mixed_window")
    b, n, nkv = q.shape[0], q.shape[2], k.shape[2]
    qk, kk, vk = (ak._to_kernel(x, n // nkv) for x in (q, k, v))
    assert qk.shape == (b * n, SEQ, 192) and kk.shape == (b * nkv, SEQ, 192)
    assert vk.shape == (b, SEQ, nkv * 128)
    scale = 1.0 / np.sqrt(192)
    out, lse = ak._flash_fwd(qk, kk, vk, (b, n, nkv), True, scale, BLOCK,
                             BLOCK, True)
    assert out.shape == (b, SEQ, n * 128)
    assert lse.shape == (b * n, SEQ // BLOCK, BLOCK) \
        and lse.dtype == jnp.float32
    for x, heads in ((qk, n), (kk, nkv), (vk, nkv), (out, n)):
        back = ak._from_kernel(x, b, heads)
        assert back.shape[:3] == (b, SEQ, heads)
    np.testing.assert_array_equal(np.asarray(ak._from_kernel(qk, b, n)),
                                  np.asarray(q))
    np.testing.assert_array_equal(np.asarray(ak._from_kernel(vk, b, nkv)),
                                  np.asarray(v))
    s = jnp.einsum("btnh,bsnh->bnts", q, jnp.repeat(k, n // nkv, axis=2)) \
        * scale
    s = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), s, -jnp.inf)
    want = jax.nn.logsumexp(s, axis=-1).reshape(lse.shape)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_the_sharded_launch_reads_shards_of_the_same_layout(small_blocks):
    """``flash_attention_sharded`` on the CPU mesh, batch over ``dp`` and
    heads over ``mp``: each shard's reshape to ``[batch, seq, heads *
    width]`` is its own, so values and gradients are the unsharded
    call's."""
    from paddle_tpu.distributed.fleet.topology import build_mesh

    mesh = build_mesh(devices=jax.devices()[:4], dp=2, mp=2)
    q, k, v, do = (_rand((2, SEQ, 4, 128), s) for s in (1, 2, 3, 4))
    k, v = k[:, :, :2], v[:, :, :2]
    got = _out_and_grads(
        lambda q, k, v: pk.flash_attention_sharded(
            q, k, v, True, mesh, interpret=True, window=100), q, k, v, do)
    want = _out_and_grads(
        lambda q, k, v: pk._xla_attention(q, k, v, is_causal=True,
                                          window=100), q, k, v, do)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-5,
                                   atol=5e-5, err_msg=name)


# ------------------------------------------------- lowered for the TPU ----

def _lowered(q, k, v, **kw):
    def f(q, k, v):
        def loss(*a):
            return jnp.sum(ak.flash_attention_pallas(
                *a, is_causal=True, **kw).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return jax.jit(f).trace(q, k, v).lower(
        lowering_platforms=("tpu",)).as_text()


def _transposed(text):
    """The result types of the lowered text's ``stablehlo.transpose``s."""
    return re.findall(r"stablehlo\.transpose.*-> (tensor<[^>]+>)", text)


@pytest.mark.parametrize("q_shape,kv_heads,window", [
    ((1, 8192, 72, 128), 8, 512),           # laguna's window layers
    ((1, 8192, 48, 128), 8, None)])         # laguna's full layers
def test_no_transpose_stands_beside_an_in_place_call(q_shape, kv_heads,
                                                     window):
    """Forward and gradients, lowered for the TPU: q, k, v and dO reach the
    custom calls as reshapes of the arguments, and O, dQ, dK and dV leave
    them as reshapes; the ONE transpose left moves delta's float32
    ``[seq, heads]`` sums to heads-first, 1 / 64 of an operand's bytes."""
    b, t, n, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16)
    text = _lowered(q, kv, kv, **({"window": window} if window else {}))
    assert text.count("tpu_custom_call") == 2
    assert _transposed(text) == [f"tensor<{b}x{n}x{t // 8}x8xf32>"]
    # what the custom calls take and give: the arguments' bytes, unmoved
    assert f"tensor<{b}x{t}x{n * d}xbf16>" in text
    assert f"tensor<{b}x{t}x{kv_heads * d}xbf16>" in text
    assert f"tensor<{b * n}x{t}x{d}xbf16>" not in text
    # the statistics: lane-dense rows, never a [seq, 1] column
    assert f"tensor<{b * n}x{t // 512}x512xf32>" in text
    assert f"x{t}x1xf32>" not in text


@pytest.mark.parametrize("q_shape,v_width", [
    ((4, 2048, 32, 128), 128),              # the GPT cells
    ((2, 8192, 32, 192), 128)])             # kanana
def test_a_call_with_a_kv_head_a_q_head_keeps_the_copy_layout(q_shape,
                                                               v_width):
    """One kv head a q head: in place measured slower on the chip (PR 35),
    so all eight operands cross as ``[batch * heads, seq, width]``: q, k,
    v, dO transposed in and dQ, dK, dV out (O's is dead code under a
    gradient; XLA folds them into the producers where it can); the
    statistics cross as rows all the same."""
    b, t, n, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, t, n, v_width), jnp.bfloat16)
    text = _lowered(q, q, v)
    moved = _transposed(text)
    assert len(moved) == 7 and all("bf16" in m for m in moved), moved
    assert f"tensor<{b * n}x{t}x{d}xbf16>" in text
    assert f"tensor<{b * n}x{t}x{v_width}xbf16>" in text
    assert f"tensor<{b}x{t}x{n * v_width}xbf16>" not in text
    assert f"tensor<{b * n}x{t // 512}x512xf32>" in text
    assert f"x{t}x1xf32>" not in text


def test_a_grouped_mixed_call_copies_its_192_wide_operands_only():
    """192 | 128 over grouped kv heads: q, k in and dQ, dK out are the four
    transposes (and delta's); v, O, dO, dV cross as ``[2, 8192, heads *
    128]``."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 8192, 8, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    text = _lowered(q, k, v)
    moved = _transposed(text)
    wide = [t for t in moved if "bf16" in t]
    assert len(wide) == 4 and all("x192x" in t.replace("xbf16", "x")
                                  for t in wide), moved
    assert [t for t in moved if "bf16" not in t] \
        == ["tensor<2x32x1024x8xf32>"]
    assert "tensor<2x8192x4096xbf16>" in text
    assert "tensor<2x8192x1024xbf16>" in text
    assert "tensor<64x8192x128xbf16>" not in text


# ------------------------------------------------------------ the record --

def test_the_record_names_what_went_in_place_and_what_was_copied():
    q, k, v, _, _ = _operands("mixed_192_over_128")
    before = pk.flash_layout_sums()
    ak.flash_attention_pallas(q, k, v, is_causal=True, interpret=True)
    rec = pk.flash_layout_log()[-1]
    assert rec["kernel"] == "flash_attention"
    assert rec["shapes"] == ("q(1, 256, 4, 192) k(1, 256, 2, 192) "
                             "v(1, 256, 2, 128)")
    assert rec["in_place"] == ("v", "o", "do", "dv")
    assert rec["copied"] == dict.fromkeys(("q", "k", "dq", "dk"),
                                          "width 192 % 128")
    after = pk.flash_layout_sums()
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 1, "flash_operands_in_place": 4,
        "flash_operands_copied": 4}
    q, k, v, _, window = _operands("window")
    ak.flash_attention_pallas(q, k, v, is_causal=True, interpret=True,
                              window=window)
    rec = pk.flash_layout_log()[-1]
    assert rec["kernel"] == "flash_window100_attention"
    assert len(rec["in_place"]) == 8 and rec["copied"] == {}
    # kanana's call: a kv head a q head, so nothing goes in place
    q, k, v, _, _ = _operands("kanana_like")
    ak.flash_attention_pallas(q, k, v, is_causal=True, interpret=True)
    rec = pk.flash_layout_log()[-1]
    assert rec["in_place"] == () and rec["copied"] == {
        **dict.fromkeys(("q", "k", "dq", "dk"), "width 192 % 128"),
        **dict.fromkeys(("v", "o", "do", "dv"), "kv heads not grouped")}


@pytest.mark.parametrize("width,in_place,copied", [(128, 16, 0),
                                                   (16, 0, 16)])
def test_the_compiled_steps_account_counts_its_flash_operands(
        monkeypatch, width, in_place, copied):
    """``TrainStep.compile_account()``: the flash calls traced while the
    step compiled, two layers' here (kernels forced on, in interpret mode,
    as on the chip)."""
    import functools

    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.laguna import laguna_tiny

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(
        ak, "flash_attention_pallas",
        functools.partial(ak.flash_attention_pallas, interpret=True))
    monkeypatch.setattr(pk, "FLASH_MIN_SEQ", 0)
    paddle.seed(0)
    model = laguna_tiny(
        num_hidden_layers=2, num_attention_heads_per_layer=(2, 2),
        layer_types=("full_attention", "sliding_attention"),
        mlp_layer_types=("dense", "dense"), head_dim=width,
        num_key_value_heads=1, hidden_size=32, intermediate_size=32,
        vocab_size=64)
    step = TrainStep(
        model, lambda logits, labels: model.loss(logits, labels),
        paddle.optimizer.AdamW(learning_rate=1e-3,
                               parameters=model.parameters()))
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 64, (1, 64)).astype(np.int32))
    step(ids, ids)
    account = step.compile_account()
    assert (account["flash_calls"], account["flash_operands_in_place"],
            account["flash_operands_copied"]) == (2, in_place, copied)
    step(ids, ids)                      # no compile, no new account
    assert step.compile_account() is account
