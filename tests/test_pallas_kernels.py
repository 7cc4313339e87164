"""Pallas kernels vs XLA reference numerics (interpret mode on CPU).

Mirrors the reference's OpTest check_output/check_grad pattern
(test/legacy_test/eager_op_test.py:377): forward compared against a
straightforward composition, gradients compared against jax.grad of that
composition.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import _xla_attention
from paddle_tpu.ops.pallas import attention_kernel
from paddle_tpu.ops.pallas.attention_kernel import (
    flash_attention_pallas,
    supports,
)


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 256, 4, 32),
                                   (1, 128, 2, 16), (1, 256, 2, 192, 128)])
def test_flash_attention_forward(shape, causal):
    """A fifth entry is v's width where it differs from q's and k's."""
    q, k = (_rand(shape[:4], s) for s in (0, 1))
    v = _rand(shape[:3] + shape[-1:], 2)
    got = flash_attention_pallas(q, k, v, is_causal=causal, interpret=True)
    want = _xla_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads", [(32, 32), (192, 128)],
                         ids=["32", "mla192|128"])
def test_flash_attention_grads(causal, heads):
    """``heads``: q/k width and v width; 192 | 128 is latent attention's
    expanded form (scores over 128 + 64 rotary, values 128 wide)."""
    qk, hv = heads
    q, k = (_rand((1, 128, 2, qk), s) for s in (3, 4))
    v = _rand((1, 128, 2, hv), 5)
    assert supports(128, 128, qk, hv)

    def loss_pallas(q, k, v):
        out = flash_attention_pallas(q, k, v, is_causal=causal,
                                     interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = _xla_attention(q, k, v, is_causal=causal)
        return jnp.sum(out * jnp.cos(out))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_uneven_seq_blocks():
    # seq 192 = 64-divisible but not 128: picks a smaller block
    shape = (1, 192, 2, 32)
    q, k, v = (_rand(shape, s) for s in (6, 7, 8))
    assert supports(192, 192, 32)
    got = flash_attention_pallas(q, k, v, is_causal=True, interpret=True)
    want = _xla_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_supports_gating():
    assert not supports(100, 100, 32)   # seq not divisible by any block
    assert not supports(128, 128, 256)  # head too large
    assert supports(1024, 1024, 64)


def test_layernorm_forward_and_grads():
    from paddle_tpu.ops.pallas.layernorm_kernel import layernorm_pallas

    x = _rand((4, 64, 128), 20)
    g = _rand((128,), 21) * 0.1 + 1.0
    b = _rand((128,), 22) * 0.1

    def ref(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    got = layernorm_pallas(x, g, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(x, g, b)),
                               rtol=1e-5, atol=1e-5)

    def loss_p(x, g, b):
        return jnp.sum(jnp.sin(layernorm_pallas(x, g, b, interpret=True)))

    def loss_r(x, g, b):
        return jnp.sum(jnp.sin(ref(x, g, b)))

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, g, b)
    for a, e, name in zip(gp, gr, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_layernorm_supports_gating():
    from paddle_tpu.ops.pallas.layernorm_kernel import supports
    assert supports(256, 128)
    assert not supports(256, 100)   # feature dim not lane-aligned
    assert not supports(7, 128)     # rows not blockable


def test_flash_attention_bf16():
    shape = (1, 128, 2, 64)
    q, k, v = (_rand(shape, s, jnp.bfloat16) for s in (9, 10, 11))
    got = flash_attention_pallas(q, k, v, is_causal=True, interpret=True)
    want = _xla_attention(q, k, v, is_causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


# bf16 keeps 8 significant bits: neighbouring values are 2**-8 (0.4%) of
# their magnitude apart.  Against a float32 reference of the same inputs the
# kernel rounds at three points: the probabilities and dS where they become
# an MXU operand (relative 2**-9 each, averaged over a row), and the result
# itself (2**-9).  Each compared tensor therefore has to stay within TWO bf16
# steps of its largest value; a wrong mask, block bound or scale shows as
# tens of steps.
BF16_STEP = 2.0 ** -8


def _dense_attention_f32(q, k, v, causal):
    """[bn, seq, head] attention in float32, nothing fused, nothing rounded."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqh,bkh->bqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(s, axis=-1), v)


def _flash_fwd_bwd(q, k, v, do, causal, block_q, block_k):
    """``_flash_fwd`` and ``_flash_bwd`` themselves on ``[heads, seq,
    head_dim]`` arrays, ONE batch row and a kv head a q head: every operand
    crosses as the copy ``[heads, seq, width]``, the log-sum-exp as
    lane-dense rows."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    heads = q.shape[0]
    dims = (1, heads, k.shape[0])
    to_kernel = lambda x: attention_kernel._to_kernel(      # noqa: E731
        x.transpose(1, 0, 2)[None], 1)
    q, k, v, do = (to_kernel(x) for x in (q, k, v, do))
    out, lse = attention_kernel._flash_fwd(q, k, v, dims, causal, scale,
                                           block_q, block_k, True)
    assert lse.shape == (heads, q.shape[1] // block_q, block_q)
    grads = attention_kernel._flash_bwd(q, k, v, out, lse, do, dims, causal,
                                        scale, block_q, block_k, True)
    return tuple(
        attention_kernel._from_kernel(x, 1, x_heads)[0].transpose(1, 0, 2)
        for x, x_heads in zip((out,) + tuple(grads),
                              (heads, heads) + dims[2:] * 2))


# seq 512 at 128 x 64 and 64 x 128: per q block some key blocks lie wholly
# below the diagonal, one or two cross it and the rest are skipped, with
# block_q != block_k both ways round; seq 192 is the uneven case (no 128
# divides it, the heuristic picks 64 x 64).
@pytest.mark.parametrize("seq,blocks", [(512, (128, 64)), (512, (64, 128)),
                                        (192, None)])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_forward_and_grads(causal, head_dim, seq, blocks):
    shape = (2, seq, head_dim)
    q, k, v, do = (_rand(shape, s, jnp.bfloat16) for s in (30, 31, 32, 33))
    if blocks is None:
        blocks = attention_kernel._blocks(seq, seq)
        assert blocks == (64, 64)
    got = _flash_fwd_bwd(q, k, v, do, causal, *blocks)
    want_out, vjp = jax.vjp(
        lambda q, k, v: _dense_attention_f32(q, k, v, causal), q, k, v)
    want = (want_out,) + vjp(do.astype(jnp.float32))
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16, name
        w = np.asarray(w, np.float32)
        err = np.abs(np.asarray(g, np.float32) - w).max()
        assert err <= 2 * BF16_STEP * np.abs(w).max(), (
            f"{name}: {err / (BF16_STEP * np.abs(w).max()):.2f} bf16 steps "
            f"off the float32 reference")


def _eqns(jaxpr, primitive):
    """The equations of one primitive in a jaxpr and all its sub-jaxprs."""
    from paddle_tpu.framework.analysis import walk_jaxprs

    return [eqn for _, sub in walk_jaxprs(jaxpr) for eqn in sub.eqns
            if eqn.primitive.name == primitive]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dots_take_the_input_dtype(dtype):
    """The MXU gets its operands in the dtype q, k, v are stored in and
    accumulates in float32.  An ``.astype(float32)`` put back before a dot
    fails here: the chip would round that operand to bf16 in the MXU's feed
    all the same, and interpret mode would no longer show it."""
    x = jax.ShapeDtypeStruct((2, 256, 64), dtype)

    def fwd_bwd(q, k, v, do):
        return _flash_fwd_bwd(q, k, v, do, True, 128, 64)

    dots = {
        call.params["name"]: [
            (tuple(v.aval.dtype for v in dot.invars),
             dot.params["preferred_element_type"])
            for dot in _eqns(call.params["jaxpr"], "dot_general")]
        for call in _eqns(jax.make_jaxpr(fwd_bwd)(x, x, x, x), "pallas_call")}
    # fwd QK^T, PV; bwd KQ^T, P^T dO, V dO^T, dS^T Q, dS K: the five the
    # mathematics needs, S and dP formed once a block pair
    assert {name: len(d) for name, d in dots.items()} == {
        "flash_attention_fwd": 2, "flash_attention_bwd_dq_dkv": 5}
    want = ((jnp.dtype(dtype),) * 2, jnp.dtype(jnp.float32))
    for name, found in dots.items():
        for operands, acc in found:
            assert (operands, jnp.dtype(acc)) == want, (name, operands, acc)


def test_flash_attention_bf16_key_bias_gradient_stays_noise():
    """A constant added to every key moves no softmax, so the gradient of a
    key bias, sum_t dk[t], is zero; what a bf16 run leaves there is rounding
    noise (the benchmark's ``zero_grad_leaf_norm`` holds a training step to
    it).  The kernel rounds dS to bf16 where it becomes an MXU operand; that
    must not lift the noise, taken in units of dk's own largest value, above
    THREE times what ``_xla_attention`` leaves on the same inputs.  Here on the
    CPU XLA keeps dS float32 and leaves only the rounding of dk itself;
    measured over 9 draws: this kernel 1.9-2.1x, the kernel with a float32
    dS 1.4-1.5x (delta is formed from the ROUNDED output, so a row of dS
    does not sum to zero exactly).  A dS scaled or rounded twice in bf16
    goes past three."""
    shape = (2, 512, 4, 64)
    q, k, v, w = (_rand(shape, s, jnp.bfloat16) for s in (40, 41, 42, 43))

    def key_bias_grad(attention):
        def loss(k):
            out = attention(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))
        dk = np.asarray(jax.grad(loss)(k), np.float32)
        return np.linalg.norm(dk.sum(axis=1)) / np.abs(dk).max()

    flash = key_bias_grad(lambda q, k, v: flash_attention_pallas(
        q, k, v, is_causal=True, interpret=True))
    xla = key_bias_grad(lambda q, k, v: _xla_attention(
        q, k, v, is_causal=True))
    assert 0 < xla and flash <= 3 * xla, (flash, xla)


# ------------------------------------------ flash under a dp x mp mesh ----

def _dp_mp_mesh(**degrees):
    from paddle_tpu.distributed.fleet.topology import build_mesh

    n = int(np.prod(list(degrees.values())))
    return build_mesh(devices=jax.devices()[:n], **degrees)


def _kernel_calls(jaxpr, name):
    """How many ``pallas_call`` equations named ``name`` a jaxpr holds,
    sub-jaxprs included (a scan's body once)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                eqn.params["name"] == name:
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub, name)
    return found


@pytest.mark.parametrize("form", ["plain", "checkpoint_in_scan",
                                  "checkpoint_by_name_in_scan"])
def test_flash_attention_sharded_matches_xla(form):
    """The kernels inside the dispatcher's shard_map on the four-axis mesh
    ``build_mesh(dp=2, mp=2)`` gives (2 x 1 x 1 x 2: two rows, four heads)
    against the XLA composition, in value and in the gradients the
    custom_vjp gives per shard; ``checkpoint_in_scan`` is the form
    ``SpmdTrainStep(remat="full")`` puts it in (a rematerialised block
    inside the scan over layers that keeps nothing), where the forward
    kernel runs twice; ``checkpoint_by_name_in_scan`` is the form
    ``SpmdTrainStep(remat=True)`` builds since PR 52 (the checkpoint with
    the by-name policy of ``fleet.recompute``: the forward's tagged
    output and row statistics cross the ``shard_map`` and the scan as
    residuals, and the forward kernel runs once)."""
    from paddle_tpu.distributed.fleet.recompute import _resolve_policy
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.ops.pallas import flash_attention_sharded

    mesh = _dp_mp_mesh(dp=2, mp=2)
    assert tuple(mesh.shape.items()) == (
        ("dp", 2), ("pp", 1), ("sharding", 1), ("mp", 2))
    q, k, v, w = (_rand((2, 128, 4, 32), s) for s in (40, 41, 42, 43))

    def loss(attn):
        def f(q, k, v):
            if form == "plain":
                return jnp.sum(attn(q, k, v) * w)

            def layer(h, _):        # over f's own q, k, v: they get gradients
                return h + attn(q + h, k, v), None

            policy = _resolve_policy(
                True if form == "checkpoint_by_name_in_scan" else "full")
            h, _ = jax.lax.scan(jax.checkpoint(layer, policy=policy),
                                jnp.zeros_like(q), None, length=2)
            return jnp.sum(h * w)
        return jax.value_and_grad(f, argnums=(0, 1, 2))

    with use_mesh(mesh):
        step = jax.jit(loss(lambda q, k, v: flash_attention_sharded(
            q, k, v, True, mesh, interpret=True)))
        got, grads = step(q, k, v)
        if form != "plain":
            # the by-name form's backward body holds no forward kernel
            forwards = _kernel_calls(step.trace(q, k, v).jaxpr.jaxpr,
                                     "flash_attention_fwd")
            assert forwards == (1 if "by_name" in form else 2), forwards
    want, ref = loss(lambda q, k, v: _xla_attention(
        q, k, v, is_causal=True))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4)
    for a, b, name in zip(grads, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")
    if form == "plain":     # dq leaves the shard_map as q went in
        assert grads[0].sharding.spec == jax.sharding.PartitionSpec(
            "dp", None, "mp")


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The flash dispatcher as it decides on a TPU, with each of its three
    ways out replaced by a recorder."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import attention_kernel as ak

    took = []
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ak, "flash_attention_pallas",
                        lambda q, k, v, causal: took.append("kernel") or v)
    monkeypatch.setattr(
        pk, "flash_attention_sharded",
        lambda q, k, v, causal, mesh: took.append(("sharded", mesh)) or v)
    monkeypatch.setattr(pk, "_xla_attention",
                        lambda q, k, v, **kw: took.append("xla") or v)
    return pk, took


def test_flash_dispatch_under_a_mesh_takes_the_sharded_launch(tpu_dispatch):
    """Where GSPMD partitions the step the dispatcher no longer gives way:
    at the four-chip cell's per-layer shape it hands the mesh it finds to
    ``flash_attention_sharded`` and announces nothing."""
    import warnings

    from paddle_tpu.distributed.fleet.spmd import use_mesh

    pk, took = tpu_dispatch
    mesh = _dp_mp_mesh(dp=2, mp=2)
    x = jax.ShapeDtypeStruct((4, 2048, 32, 128), jnp.bfloat16)
    attn = lambda q: pk.flash_attention(q, q, q, is_causal=True)  # noqa: E731
    with warnings.catch_warnings():
        warnings.simplefilter("error", pk.KernelFallbackWarning)
        jax.eval_shape(attn, x)
        with use_mesh(mesh):
            jax.eval_shape(attn, x)
    assert took == ["kernel", ("sharded", mesh)]


@pytest.mark.parametrize("case,reason", [
    ("heads_not_divisible", r"3 heads do not divide over mp of 2"),
    ("batch_not_divisible", r"batch 3 does not divide over mesh axes "
                            r"\('dp',\) of 2"),
    ("sep_axis", r"sep axis of 2: context parallelism has its own path"),
    ("attn_mask", r"takes no attn_mask, dropout or scale"),
    # spmd_pipeline at pp > 1 is manual over 'pp' alone: the announced
    # fallback was kept, no second shard_map is nested
    ("inside_partial_shard_map", r"GSPMD cannot partition a Mosaic kernel.*"
                                 r"manual over \('pp',\) only"),
])
def test_flash_dispatch_under_a_mesh_refuses_aloud(tpu_dispatch, case,
                                                   reason):
    """What the sharded launch cannot serve takes ``_xla_attention`` as
    before, with a warning that says which condition failed."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.fleet.spmd import use_mesh

    pk, took = tpu_dispatch
    degrees, shape, mask = dict(dp=2, mp=2), (2, 1024, 4, 32), None
    if case == "heads_not_divisible":
        shape = (2, 1024, 3, 32)
    elif case == "batch_not_divisible":
        shape = (3, 1024, 4, 32)
    elif case == "sep_axis":
        degrees = dict(dp=2, sep=2)
    elif case == "attn_mask":
        mask = jnp.ones((1024, 1024), bool)
    elif case == "inside_partial_shard_map":
        degrees = dict(dp=2, pp=2)
    mesh = _dp_mp_mesh(**degrees)
    x = jnp.zeros(shape, jnp.bfloat16)

    def attn(q):
        return pk.flash_attention(q, q, q, attn_mask=mask, is_causal=True)

    if case == "inside_partial_shard_map":
        attn = jax.shard_map(attn, mesh=mesh, in_specs=P(), out_specs=P(),
                             axis_names={"pp"}, check_vma=False)
    with use_mesh(mesh), pytest.warns(pk.KernelFallbackWarning,
                                      match=reason):
        jax.eval_shape(attn, x)
    assert took == ["xla"]


class TestDecodeAttention:
    def _mk(self, B=3, NQ=4, NKV=2, D=16, S=64, seed=0):
        import jax.numpy as jnp

        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.rand(B, NQ, D).astype(np.float32))
        k = jnp.asarray(rng.rand(B, S, NKV, D).astype(np.float32))
        v = jnp.asarray(rng.rand(B, S, NKV, D).astype(np.float32))
        lens = jnp.asarray(rng.randint(1, S + 1, B).astype(np.int32))
        return q, k, v, lens

    def test_matches_xla_reference_ragged_gqa(self):
        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_pallas,
            decode_attention_xla,
            supports,
        )

        q, k, v, lens = self._mk()
        assert supports(64, 16, 4, 2)
        out = decode_attention_pallas(q, k, v, lens, interpret=True)
        ref = decode_attention_xla(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_mha_case_and_tiny_lengths(self):
        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_pallas,
            decode_attention_xla,
        )
        import jax.numpy as jnp

        q, k, v, _ = self._mk(NQ=2, NKV=2, seed=1)
        lens = jnp.asarray(np.array([1, 64, 33], np.int32))
        out = decode_attention_pallas(q, k, v, lens, interpret=True)
        ref = decode_attention_xla(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # length=1 row attends only position 0 == v[:, 0]
        np.testing.assert_allclose(
            np.asarray(out)[0, 0], np.asarray(v)[0, 0, 0], atol=2e-5)

    def test_empty_sequence_emits_zeros(self):
        """Advisor round-2 regression: lengths[b]==0 used to degenerate the
        online softmax into a uniform average over the uninitialized cache."""
        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_pallas,
            decode_attention_xla,
        )
        import jax.numpy as jnp

        q, k, v, _ = self._mk(seed=3)
        lens = jnp.asarray(np.array([0, 17, 0], np.int32))
        out = decode_attention_pallas(q, k, v, lens, interpret=True)
        ref = decode_attention_xla(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out)[0], 0.0)
        np.testing.assert_allclose(np.asarray(out)[2], 0.0)
        np.testing.assert_allclose(np.asarray(ref)[0], 0.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_api_entry_matches_and_jits(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as IF
        from paddle_tpu.jit import to_static
        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_xla,
        )

        q, k, v, lens = self._mk(seed=2)
        out = IF.ragged_decode_attention(
            paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
            paddle.to_tensor(np.asarray(v)),
            paddle.to_tensor(np.asarray(lens)), interpret=True)
        ref = decode_attention_xla(q, k, v, lens)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=2e-5)

        # under jit tracing the XLA fallback path must compile + match
        @to_static
        def step(qq, kk, vv, ll):
            return IF.ragged_decode_attention(qq, kk, vv, ll)

        out2 = step(paddle.to_tensor(np.asarray(q)),
                    paddle.to_tensor(np.asarray(k)),
                    paddle.to_tensor(np.asarray(v)),
                    paddle.to_tensor(np.asarray(lens)))
        np.testing.assert_allclose(out2.numpy(), np.asarray(ref),
                                   atol=2e-5)


class TestRaggedAttention:
    """Interpret-mode parity battery for the unified ragged paged
    attention kernel — the registry's K005 contract points at
    ``test_mixed_batch_parity`` by name.  Every case checks the Pallas
    kernel against the bitwise-defined masked-XLA fallback
    (``paged_ragged_attention_xla``) on the SAME descriptors."""

    def _pool(self, NB=6, BS=8, NKV=2, D=16, seed=0):
        rng = np.random.RandomState(seed)
        # drawn token-major, stored head-major [NB, NKV, BS, D]
        k = jnp.asarray(rng.rand(NB, BS, NKV, D).astype(np.float32))
        v = jnp.asarray(rng.rand(NB, BS, NKV, D).astype(np.float32))
        return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def _token_descriptors(self, T, row_start, row_qlen, row_pos0):
        """The per-token (ctx, rows) form of the per-row descriptors —
        the dual-descriptor contract of paged_ragged_attention."""
        ctx = np.zeros(T, np.int32)
        rows = np.zeros(T, np.int32)
        for r in range(len(row_start)):
            s, n, p0 = int(row_start[r]), int(row_qlen[r]), \
                int(row_pos0[r])
            ctx[s:s + n] = p0 + np.arange(1, n + 1)
            rows[s:s + n] = r
        return jnp.asarray(ctx), jnp.asarray(rows)

    def _check(self, q, kp, vp, bt, row_start, row_qlen, row_pos0):
        from paddle_tpu.inference.llm.paged_attention import (
            paged_ragged_attention_xla,
        )
        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            paged_ragged_attention_pallas,
        )

        ctx, rows = self._token_descriptors(q.shape[0], row_start,
                                            row_qlen, row_pos0)
        got = paged_ragged_attention_pallas(
            q, kp, vp, bt, jnp.asarray(row_start, jnp.int32),
            jnp.asarray(row_qlen, jnp.int32),
            jnp.asarray(row_pos0, jnp.int32), interpret=True)
        ref = paged_ragged_attention_xla(q, kp, vp, bt, ctx, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        return np.asarray(got)

    def test_mixed_batch_parity(self):
        """One launch, all three phases at once through scattered
        non-identity tables with GQA folding (4 query heads on 2 KV
        heads): a decode row deep in its sequence, a prefill chunk that
        STRADDLES a page boundary (positions 5..10 over 8-token pages),
        a speculative-verify row (3 consecutive positions), and a dead
        row — whose tokens, like the bucket padding, must come back as
        EXACT zeros, not averaged garbage pages."""
        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 16
        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            supports,
        )
        assert supports(BS, D, NQ, NKV, T)
        kp, vp = self._pool(NB, BS, NKV, D, seed=30)
        rng = np.random.RandomState(31)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[5, 2, 0], [4, 1, 3], [0, 3, 5],
                                   [2, 2, 2]], np.int32))
        row_start = [0, 1, 7, 0]
        row_qlen = [1, 6, 3, 0]          # decode, chunk, verify, dead
        row_pos0 = [9, 5, 3, 0]
        got = self._check(q, kp, vp, bt, row_start, row_qlen, row_pos0)
        dead = np.ones(T, bool)
        for s, n in zip(row_start, row_qlen):
            dead[s:s + n] = False
        assert np.all(got[dead] == 0.0), "padding tokens not exact zero"

    def test_pure_decode_rows(self):
        """A full batch of one-token rows (the plain decode step),
        including an empty sequence (qlen 0 -> exact zeros) and a
        partial last page (13 = 8 + 5)."""
        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 8
        kp, vp = self._pool(NB, BS, NKV, D, seed=32)
        rng = np.random.RandomState(33)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(rng.randint(0, NB, size=(T, 3)).astype(np.int32))
        lens = np.array([0, 13, 24, 5, 1, 8, 16, 9], np.int32)
        row_start = np.arange(T, dtype=np.int32)
        row_qlen = (lens > 0).astype(np.int32)
        row_pos0 = np.maximum(lens - 1, 0).astype(np.int32)
        got = self._check(q, kp, vp, bt, row_start, row_qlen, row_pos0)
        np.testing.assert_allclose(got[0], 0.0)      # empty slot

        # the public entry point (decode batch as B rows of one slot)
        # must route through the ragged kernel and agree with ITS
        # fallback bitwise-meaningfully too
        from ragged_rows import rows_attention
        via = rows_attention(q[:, None], kp, vp, bt, lens[:, None],
                             interpret=True)
        ref = rows_attention(q[:, None], kp, vp, bt, lens[:, None])
        np.testing.assert_allclose(np.asarray(via), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_pure_prefill_row_page_straddle(self):
        """A single chunk occupying the whole token axis, starting
        mid-page (positions 5..12 with 8-token pages): causality inside
        the chunk AND readback of earlier pages through the table."""
        NB, BS, NQ, NKV, D, C = 6, 8, 4, 2, 16, 8
        kp, vp = self._pool(NB, BS, NKV, D, seed=40)
        rng = np.random.RandomState(41)
        q = jnp.asarray(rng.rand(C, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[3, 1, 4, 0]], np.int32))
        for start in (0, 5):     # page-aligned and straddling starts
            self._check(q, kp, vp, bt, [0], [C], [start])

        # the public entry point (one chunk row, traced start
        # included) rides the ragged kernel and must match its own XLA
        # fallback
        from ragged_rows import rows_attention
        f = jax.jit(lambda s: rows_attention(
            q[None], kp, vp, bt, (s + 1 + jnp.arange(C))[None],
            interpret=True))
        got = f(jnp.asarray(5, jnp.int32))
        ref = rows_attention(q[None], kp, vp, bt,
                             (6 + jnp.arange(C))[None])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_verify_rows_no_table_replication(self):
        """Speculative verify through the ragged kernel: each
        sequence's K+1 tokens share ONE block-table row (the retired
        path materialized jnp.repeat(block_tables, K+1, axis=0)), and
        per-token causality masks the later drafts already scattered
        into the pool."""
        from ragged_rows import rows_attention

        NB, BS, NQ, NKV, D = 6, 8, 4, 2, 16
        B, TV = 4, 4                       # B*TV = 16 flat tokens
        kp, vp = self._pool(NB, BS, NKV, D, seed=50)
        rng = np.random.RandomState(51)
        q = jnp.asarray(rng.rand(B, TV, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[5, 2, 0], [4, 1, 3], [0, 3, 5],
                                   [2, 2, 2]], np.int32))
        # live prefixes of 4/2/0/3 verify slots at staggered depths
        ctx = np.zeros((B, TV), np.int32)
        ctx[0, :4] = 9 + np.arange(4)
        ctx[1, :2] = 13 + np.arange(2)
        ctx[3, :3] = 5 + np.arange(3)
        ctx = jnp.asarray(ctx)
        got = rows_attention(q, kp, vp, bt, ctx, interpret=True)
        ref = rows_attention(q, kp, vp, bt, ctx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(got)[2], 0.0)  # dead row

    def test_gqa_group_of_four(self):
        """8 query heads on 2 KV heads (G = 4): the flat (token, group)
        axis folds 4 query rows per token and must still mask per
        TOKEN, not per flat row."""
        NB, BS, NQ, NKV, D, T = 6, 8, 8, 2, 16, 8
        kp, vp = self._pool(NB, BS, NKV, D, seed=60)
        rng = np.random.RandomState(61)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[1, 4, 2], [3, 0, 5]], np.int32))
        self._check(q, kp, vp, bt, [0, 3], [3, 5], [6, 0])


class TestRaggedAttentionQuant:
    """Interpret-mode parity battery for the INT8-pool ragged kernel —
    the registry's K005 contract points at ``test_mixed_batch_parity``
    by name.  The pools are genuinely quantized (quantize_kv_rows per
    (token, head) row, the engine's append-time layout) and every case
    checks the in-kernel-dequant Pallas path against the dequant-gather
    masked-XLA fallback (``paged_ragged_attention_quant_xla``) on the
    SAME descriptors."""

    def _qpool(self, NB=6, BS=8, NKV=2, D=16, seed=0):
        from paddle_tpu.inference.llm.quant import quantize_kv_rows

        rng = np.random.RandomState(seed)
        out = []
        for _ in range(2):
            f = jnp.asarray(rng.randn(NB, BS, NKV, D).astype(np.float32))
            q, s = quantize_kv_rows(f)           # s: [NB, BS, NKV]
            out += [jnp.transpose(q, (0, 2, 1, 3)),   # pool layouts:
                    jnp.transpose(s, (0, 2, 1))]      # head-major
        kq, ks, vq, vs = out
        return kq, vq, ks, vs

    def _token_descriptors(self, T, row_start, row_qlen, row_pos0):
        ctx = np.zeros(T, np.int32)
        rows = np.zeros(T, np.int32)
        for r in range(len(row_start)):
            s, n, p0 = int(row_start[r]), int(row_qlen[r]), \
                int(row_pos0[r])
            ctx[s:s + n] = p0 + np.arange(1, n + 1)
            rows[s:s + n] = r
        return jnp.asarray(ctx), jnp.asarray(rows)

    def _check(self, q, kq, vq, ks, vs, bt, row_start, row_qlen,
               row_pos0):
        from paddle_tpu.inference.llm.paged_attention import (
            paged_ragged_attention_quant_xla,
        )
        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            paged_ragged_attention_quant_pallas,
        )

        ctx, rows = self._token_descriptors(q.shape[0], row_start,
                                            row_qlen, row_pos0)
        got = paged_ragged_attention_quant_pallas(
            q, kq, vq, ks, vs, bt, jnp.asarray(row_start, jnp.int32),
            jnp.asarray(row_qlen, jnp.int32),
            jnp.asarray(row_pos0, jnp.int32), interpret=True)
        ref = paged_ragged_attention_quant_xla(q, kq, vq, ks, vs, bt,
                                               ctx, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        return np.asarray(got)

    def test_mixed_batch_parity(self):
        """One launch, all three phases at once through scattered
        non-identity tables with GQA folding, on an int8 pool: a decode
        row deep in its sequence, a page-straddling prefill chunk, a
        speculative-verify row, and a dead row whose tokens — like the
        bucket padding — must come back as EXACT zeros even though the
        dead rows' scale entries are nonzero garbage."""
        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 16
        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            supports,
        )
        assert supports(BS, D, NQ, NKV, T)
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=70)
        rng = np.random.RandomState(71)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[5, 2, 0], [4, 1, 3], [0, 3, 5],
                                   [2, 2, 2]], np.int32))
        row_start = [0, 1, 7, 0]
        row_qlen = [1, 6, 3, 0]          # decode, chunk, verify, dead
        row_pos0 = [9, 5, 3, 0]
        got = self._check(q, kq, vq, ks, vs, bt, row_start, row_qlen,
                          row_pos0)
        dead = np.ones(T, bool)
        for s, n in zip(row_start, row_qlen):
            dead[s:s + n] = False
        assert np.all(got[dead] == 0.0), "padding tokens not exact zero"

    def test_decode_rows_partial_page(self):
        """A full batch of one-token decode rows at depths that leave
        the last page partially filled (13 = 8 + 5), plus an empty
        sequence that must emit exact zeros."""
        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 8
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=72)
        rng = np.random.RandomState(73)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(rng.randint(0, NB, size=(T, 3)).astype(np.int32))
        lens = np.array([0, 13, 24, 5, 1, 8, 16, 9], np.int32)
        row_start = np.arange(T, dtype=np.int32)
        row_qlen = (lens > 0).astype(np.int32)
        row_pos0 = np.maximum(lens - 1, 0).astype(np.int32)
        got = self._check(q, kq, vq, ks, vs, bt, row_start, row_qlen,
                          row_pos0)
        np.testing.assert_allclose(got[0], 0.0)      # empty slot

    def test_gqa_group_of_four(self):
        """8 query heads on 2 KV heads (G = 4) over the int8 pool: the
        per-head scales broadcast across the whole query-head group."""
        NB, BS, NQ, NKV, D, T = 6, 8, 8, 2, 16, 8
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=74)
        rng = np.random.RandomState(75)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[1, 4, 2], [3, 0, 5]], np.int32))
        self._check(q, kq, vq, ks, vs, bt, [0, 3], [3, 5], [6, 0])

    def test_scattered_tables_shared_pages(self):
        """Two rows aliasing the SAME physical pages through different
        logical positions (prefix sharing after a fork): dequant reads
        the one (page, head, slot) scale regardless of which row is
        looking."""
        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 8
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=76)
        rng = np.random.RandomState(77)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[3, 1, 0], [3, 1, 5]], np.int32))
        self._check(q, kq, vq, ks, vs, bt, [0, 4], [4, 4], [10, 17])

    def test_dequant_matches_full_precision_within_step(self):
        """End-to-end sanity on the approximation itself: attention
        over the int8 pool must land within the per-row quantization
        error of attention over the dequantized-f32 pool (NOT the exact
        pre-quantization values — that error is the feature's price)."""
        from paddle_tpu.inference.llm.paged_attention import (
            paged_ragged_attention_quant_xla,
            paged_ragged_attention_xla,
        )
        from paddle_tpu.inference.llm.quant import dequantize_kv_rows

        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 4
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=78)
        rng = np.random.RandomState(79)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(np.array([[0, 1, 2], [3, 4, 5]], np.int32))
        ctx, rows = self._token_descriptors(T, [0, 2], [2, 2], [12, 20])
        got = paged_ragged_attention_quant_xla(q, kq, vq, ks, vs, bt,
                                               ctx, rows)
        # dequantize the pools on the host and run the f32 reference
        kf = dequantize_kv_rows(kq, ks)     # pools are head-major
        vf = dequantize_kv_rows(vq, vs)
        ref = paged_ragged_attention_xla(q, kf.astype(jnp.float32),
                                         vf.astype(jnp.float32), bt,
                                         ctx, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_dispatcher_interpret_route(self):
        """``paged_ragged_attention`` over an int8 cache view (scale
        leaves present) with interpret=True takes the quant Pallas
        route on CPU and agrees with its fallback."""
        from paddle_tpu.inference.llm.paged_attention import (
            paged_ragged_attention,
            paged_ragged_attention_quant_xla,
        )

        NB, BS, NQ, NKV, D, T = 6, 8, 4, 2, 16, 8
        kq, vq, ks, vs = self._qpool(NB, BS, NKV, D, seed=80)
        rng = np.random.RandomState(81)
        q = jnp.asarray(rng.rand(T, NQ, D).astype(np.float32))
        bt = jnp.asarray(rng.randint(0, NB, size=(T, 2)).astype(np.int32))
        row_start = np.arange(T, dtype=np.int32)
        row_qlen = np.ones(T, np.int32)
        row_pos0 = np.asarray([3, 0, 9, 7, 1, 15, 4, 11], np.int32)
        ctx, rows = self._token_descriptors(
            T, row_start, row_qlen, row_pos0)
        got = paged_ragged_attention(
            q, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}, bt,
            ctx, rows, jnp.asarray(row_start), jnp.asarray(row_qlen),
            jnp.asarray(row_pos0), interpret=True)
        ref = paged_ragged_attention_quant_xla(q, kq, vq, ks, vs, bt,
                                               ctx, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# ------------------------------------------- latent attention's expansion --

# heads, nope, rope, v: the kanana cell's head geometry and mla_moe_tiny()'s
MLA_GEOMETRY = {"cell": (32, 128, 64, 128), "tiny": (4, 32, 16, 32)}


def _mla_expand_case(geometry, dtype, seed=0, batch=2, seq=64):
    from paddle_tpu.models.llama import _rope_tables

    heads, nope, rope, v_dim = MLA_GEOMETRY[geometry]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(batch, seq, heads, nope + rope), (batch, seq, heads,
                                                 nope + v_dim),
              (batch, seq, rope)]
    operands = [jax.random.normal(k, s, jnp.float32).astype(dtype)
                for k, s in zip(keys, shapes)]
    cos, sin = (jnp.asarray(t) for t in _rope_tables(rope, seq, 1e6))
    weights = [jax.random.normal(k, s, jnp.float32).astype(dtype)
               for k, s in zip(keys[3:], [shapes[0], shapes[0],
                                          (batch, seq, heads, v_dim)])]
    return operands, (cos, sin), weights, nope


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "halves"])
@pytest.mark.parametrize("geometry", list(MLA_GEOMETRY))
def test_mla_expand_kernels_match_the_composition(geometry, interleave,
                                                  dtype):
    """``mla_expand_fwd`` / ``mla_expand_bwd`` (interpret mode, two row
    blocks a row) against ``_xla_mla_expand_qkv``: q, k and v BIT FOR BIT;
    what the backward only moves (the first ``nope`` lanes' and v's
    gradients) bit for bit too, what it rotates back to ONE rounding of the
    float32 result, ``k_rope``'s gradient (the float32 sum over the heads)
    among them, where the composition rounds that sum first."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas.mla_expand_kernel import mla_expand_pallas

    operands, tables, weights, nope = _mla_expand_case(geometry, dtype)

    def composed(*o):
        return pk._xla_mla_expand_qkv(*o, *tables, nope=nope,
                                      interleave=interleave)

    def kernels(*o):
        return mla_expand_pallas(*o, *tables, nope=nope,
                                 interleave=interleave, interpret=True,
                                 block_rows=32)

    def weighed(f):
        return lambda *o: sum(
            jnp.sum(x.astype(jnp.float32) * w.astype(jnp.float32))
            for x, w in zip(f(*o), weights))

    want, got = jax.jit(composed)(*operands), jax.jit(kernels)(*operands)
    for name, w, g in zip("qkv", want, got):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32), name)
    grads = jax.jit(jax.grad(weighed(kernels), (0, 1, 2)))(*operands)
    same = jax.jit(jax.grad(weighed(composed), (0, 1, 2)))(*operands)
    exact = jax.jit(jax.grad(weighed(composed), (0, 1, 2)))(
        *(o.astype(jnp.float32) for o in operands))
    np.testing.assert_array_equal(np.asarray(grads[0][..., :nope], "f4"),
                                  np.asarray(same[0][..., :nope], "f4"))
    np.testing.assert_array_equal(np.asarray(grads[1], "f4"),
                                  np.asarray(same[1], "f4"))
    # one rounding: half a unit in the last place, a whole one where the
    # float32 sums' orders tip a tie
    rtol = 2.0 ** -7 if dtype == jnp.bfloat16 else 2e-6
    for name, g, e in zip(("dq", "dkv_b", "dk_rope"), grads, exact):
        assert g.dtype == dtype, name
        e = np.asarray(e, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), e, rtol=rtol,
                                   atol=1e-5 * float(np.abs(e).max()),
                                   err_msg=name)


def test_mla_expand_supports_gating():
    from paddle_tpu.ops.pallas.mla_expand_kernel import (_heads_per_step,
                                                         _pick_rows,
                                                         supports)

    bf = jnp.bfloat16
    assert supports(8192, 32, 128, 64, 128, bf)           # the cell's
    assert _heads_per_step(32, 128, 64, 128) == 2
    assert _pick_rows(8192, 32, 128, 64, 128, bf) == 1024
    assert _pick_rows(8192, 32, 128, 64, 128, jnp.float32) == 512
    assert supports(4096, 16, 128, 128, 128, jnp.float32)  # a head a tile
    assert not supports(8192, 32, 96, 64, 128, bf)        # k cut off a tile
    assert not supports(8192, 32, 128, 64, 64, bf)        # v half a tile
    assert not supports(8192, 32, 128, 63, 128, bf)       # an odd pair
    assert not supports(8192, 3, 128, 64, 128, bf)        # heads unpaired
    assert not supports(8200, 32, 128, 64, 128, bf)       # rows
    assert not supports(8192, 32, 128, 64, 128, jnp.float16)
    assert not supports(64, *MLA_GEOMETRY["tiny"], bf)    # mla_moe_tiny()
