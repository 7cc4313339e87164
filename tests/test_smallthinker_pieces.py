"""The pieces ``models/smallthinker.py`` adds to the shared code, one by one
at sizes the CPU runs (the whole model against its reference is
``tests/test_smallthinker.py``): one block of each kind against the
reference's block and the benchmark's four planted faults against it, the
four shares of an expert layer whose router reads another tensor than its
experts, the ``reglu`` body through both ways the routed block runs its
experts, a layer without a rotation beside one with, ``router_input=None``
bit for bit, ``moe_tokens_unserved`` against a count by hand, and the flash
kernels in interpret mode at a group of 7 under a window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoELayer,
                                                        dropless)
from paddle_tpu.jit import functional_call
from paddle_tpu.models import laguna, moe_decoder
from paddle_tpu.models.smallthinker import SmallThinkerConfig
from paddle_tpu.ops.pallas import attention_kernel as ak

from chipbench.reference import smallthinker as ref
from chipbench.runners import smallthinker_train as runner
from chipbench.tests.test_smallthinker_runner import FAULTS, plant

from test_smallthinker import BASE, SHARES

UNCUT = runner.model_group({**BASE, **SHARES["uncut"]})


def _layer_params(m, layer=1, seed=3):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.layer_params(ref.seed_key(seed), layer, m, jnp.float32))


def _rand(shape, seed, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * np.random.RandomState(seed).randn(*shape),
                       dtype)


# ------------------------------------------- one block against the reference
def _block_loss_and_grads(layer_idx, x, do):
    """The program's block ``layer_idx`` on the reference's seeded leaves:
    ``(out, counts, unserved, d x, {leaf: gradient})``."""
    paddle.seed(0)
    block = moe_decoder.MoeDecoderLayer(runner.model_config(UNCUT), layer_idx)
    p = _layer_params(UNCUT, layer_idx)
    names = set(block.state_dict())
    assert names == set(p), names ^ set(p)

    def f(p, x):
        out, counts, _, unserved = functional_call(block, p, x[None])
        return jnp.sum(out[0] * do), (out[0], counts, unserved)

    with jax.default_matmul_precision("highest"):
        (_, (out, counts, unserved)), (dp, dx) = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(p, x)
    return out, counts, unserved, dx, dp


def _ref_block(layer_idx, x, do):
    p = _layer_params(UNCUT, layer_idx)
    kind = ref.layer_kind(UNCUT, layer_idx)

    def f(p, x):
        out, counts, unserved = ref.block(x, p, kind, UNCUT)
        return jnp.sum(out * do), (out, counts, unserved)

    with jax.default_matmul_precision("highest"):
        (_, (out, counts, unserved)), (dp, dx) = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(p, x)
    return out, counts, unserved, dx, dp


@pytest.mark.parametrize("layer_idx", [0, 1])
@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_one_block_matches_the_reference_and_a_planted_fault_does_not(
        monkeypatch, layer_idx, fault):
    """Layer 0 is full attention with NO position encoding, layer 1 a window
    layer with the rotation.  A fault that lands on the other kind of layer
    leaves this one sound."""
    lands = {None: (), "router_reads_normed": (0, 1), "rope_on_full": (0,),
             "window_plus_one": (1,), "silu_body": (0, 1)}[fault]
    if fault is not None:
        plant(monkeypatch, fault)
    x, do = _rand((40, 64), 1), _rand((40, 64), 2)
    got = _block_loss_and_grads(layer_idx, x, do)
    want = _ref_block(layer_idx, x, do)
    close = all(
        np.allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                    atol=2e-5 * float(np.abs(np.asarray(w)).max()))
        for g, w in ((got[0], want[0]), (got[3], want[3]),
                     *((got[4][n], want[4][n]) for n in want[4])))
    same_counts = np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if layer_idx in lands:
        assert not close, fault
        assert same_counts == (fault != "router_reads_normed")
    else:
        assert close and same_counts, fault
        assert int(got[2]) == int(want[2]) == 0          # nothing is cut


# ------------------------------------------------------------- the share --
def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """4 chips x 2 experts of an 8-expert router, top-3, NO shared expert:
    the routed parts the shares' ``DroplessMoELayer``s give add up to the
    uncut REFERENCE layer, nothing being counted once, with the router on
    ``x`` and the experts on another tensor ``b``; the shares' counts are
    its counts side by side, and a token none of whose experts is held gets
    exactly nothing."""
    p_all = _layer_params(UNCUT)
    x, b = _rand((40, 64), 1), _rand((40, 64), 2)
    with jax.default_matmul_precision("highest"):
        idx, w = ref.route(x, p_all["moe.router.weight"], UNCUT)
        other_idx, _ = ref.route(b, p_all["moe.router.weight"], UNCUT)
        assert not np.array_equal(np.asarray(idx), np.asarray(other_idx))
        want, want_counts, none = ref.expert_ffn(b, idx, w, p_all, UNCUT)
        assert int(none) == 0
        total, counts, unserved = 0.0, [], []
        for chip in range(4):
            m = runner.model_group({
                **BASE, "moe_num_primary_experts": 2,
                "deployment": {"router_experts": 8,
                               "expert_offset": 2 * chip}})
            p = _layer_params(m)
            # an expert's weights are drawn from its GLOBAL index
            np.testing.assert_array_equal(
                np.asarray(p["moe.experts.down"]),
                np.asarray(p_all["moe.experts.down"][2 * chip:2 * chip + 2]))
            layer = DroplessMoELayer(
                64, 32, 8, 3, num_local_experts=2, expert_offset=2 * chip,
                score_func="softmax", body="reglu")
            missing, unexpected = layer.set_state_dict(
                {n[len("moe."):]: Tensor(a) for n, a in p.items()
                 if n.startswith("moe.")})
            assert not missing and not unexpected
            part = layer(Tensor(b), router_input=Tensor(x))._data
            total = total + part
            counts.append(np.asarray(layer.tokens_per_expert))
            unserved.append(int(layer.tokens_unserved()))
            # the reference's own share says the same
            ref_part, c, u = ref.expert_ffn(b, idx, w, p, m)
            np.testing.assert_allclose(np.asarray(ref_part),
                                       np.asarray(part), rtol=1e-4,
                                       atol=1e-6)
            np.testing.assert_array_equal(np.asarray(c), counts[-1])
            # by hand: the tokens none of whose three experts is 2c, 2c + 1
            by_hand = int(np.sum(~np.isin(np.asarray(idx),
                                          [2 * chip, 2 * chip + 1])
                                 .any(axis=1)))
            assert unserved[-1] == int(u) == by_hand > 0
            nothing = np.asarray(jnp.all(part == 0, axis=1))
            assert nothing.sum() == by_hand
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.sum(want_counts)) == 40 * 3       # top-3, nothing dropped


# ------------------------------------------------------------- the body --
@pytest.mark.parametrize("body", ["reglu", "swiglu"])
def test_a_gated_body_through_both_ways_against_a_loop_over_experts(body):
    """``experts_mlp`` (the forward) and ``_experts_mlp_vjp`` (what the
    routed block's backward calls by name) against one expert after the
    other, rows behind the last group left alone."""
    groups, k, inner = 3, 16, 8
    counts = jnp.asarray([5, 0, 9], jnp.int32)
    xs, d_ys = _rand((16, k), 1), _rand((16, k), 2)
    w_in, w_out = _rand((groups, k, 2 * inner), 3, scale=0.3), \
        _rand((groups, inner, k), 4, scale=0.3)
    act = {"reglu": jax.nn.relu, "swiglu": jax.nn.silu}[body]

    def loop(xs, w_in, w_out):
        rows, first = [], 0
        for g, n in enumerate(np.asarray(counts)):
            gu = xs[first:first + n] @ w_in[g]
            rows.append((act(gu[:, :inner]) * gu[:, inner:]) @ w_out[g])
            first += n
        return jnp.concatenate(rows)

    served = int(np.sum(counts))
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(loop, xs, w_in, w_out)
        want_grads = pull(d_ys[:served])
        got = dropless.experts_mlp(xs, w_in, w_out, counts, body)
        also, vjp = dropless._experts_mlp_vjp(xs, w_in, w_out, counts, body)
        d_xs, d_in, d_out = vjp(d_ys)
    for g in (got, also):
        np.testing.assert_allclose(np.asarray(g[:served]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_xs[:served]),
                               np.asarray(want_grads[0][:served]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_in), np.asarray(want_grads[1]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_out), np.asarray(want_grads[2]),
                               rtol=1e-5, atol=1e-6)
    assert dropless.BODIES[body][1] == 2
    held = DroplessMoELayer(k, inner, 4, 2, body=body).experts
    assert held.gate_up.shape == [4, k, 2 * inner] and held.w_in is \
        held.gate_up


# ----------------------------------------------------------- no rotation --
def test_a_layer_without_a_rotation_beside_one_with():
    """``rope_params=None``: no tables, nothing rotated, and the layer's
    scores do not depend on where a token stands but through the causal
    mask: the last row of a row reversed in its first keys is unchanged,
    which the rotated layer's is not."""
    assert laguna.rope_tables(16, 8, None) == (None, None, 0)
    cos, sin, rot = laguna.rope_tables(16, 8, {"rope_theta": 1500000})
    assert cos.shape == sin.shape == (8, 8) and rot == 16
    x = _rand((1, 12, 64), 5)
    flipped = jnp.concatenate([x[:, :11][:, ::-1], x[:, 11:]], axis=1)
    last = {}
    for name, params in (("none", None), ("rope", {"rope_theta": 100.0})):
        paddle.seed(0)
        attn = laguna.GroupedGatedAttention(64, 6, 2, 16, params, 0.2, 0.2,
                                            gate=False)
        assert (attn.rope(12)[0] is None) == (name == "none")
        last[name] = [np.asarray(attn(Tensor(a))._data[0, -1])
                      for a in (x, flipped)]
    np.testing.assert_allclose(*last["none"], rtol=1e-5, atol=1e-6)
    assert np.abs(last["rope"][0] - last["rope"][1]).max() > 1e-3


# ------------------------------------------- router_input and the others --
@pytest.mark.parametrize("kw", [
    dict(num_shared_experts=1, routed_scaling_factor=2.5),      # kanana's
    dict(num_shared_experts=1, score_func="softmax"),           # laguna's
    dict(score_func="softmax", num_local_experts=4, expert_offset=2),  # sdar
    dict(body="relu2", d_latent=32, d_shared=48),       # the hybrid family's
], ids=["sigmoid-shared", "softmax-shared", "softmax-share", "relu2-latent"])
def test_without_a_router_input_a_layer_is_what_it_was(kw):
    """``router_input=None`` is the call every other family makes: the SAME
    traced program, equation for equation; handing the experts' own input
    as the router's changes no bit of the result either (the latent and the
    shared expert keep reading ``x``).  Jitted: two eager calls of one layer
    do not agree bit for bit on this host."""
    paddle.seed(3)
    layer = DroplessMoELayer(64, 32, 8, 3, **kw)
    p = {n: a._data for n, a in layer.state_dict().items()}
    x, other = _rand((2, 20, 64), 6), _rand((2, 20, 64), 7)

    def call(**more):
        def f(p, x, r):
            out = functional_call(layer, p, x, **{
                k: r if v == "r" else v for k, v in more.items()})
            return out, layer.tokens_per_expert._data
        return f

    plain, counts = jax.jit(call())(p, x, x)
    assert str(jax.make_jaxpr(call())(p, x, x)) \
        == str(jax.make_jaxpr(call(router_input=None))(p, x, x))
    same, same_counts = jax.jit(call(router_input="r"))(p, x, x)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(same))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(same_counts))
    elsewhere, _ = jax.jit(call(router_input="r"))(p, x, other)
    assert np.abs(np.asarray(elsewhere) - np.asarray(plain)).max() > 1e-6


def test_the_shells_option_is_off_for_the_other_families():
    assert moe_decoder.MoeDecoderConfig.router_reads_block_input is False
    assert SmallThinkerConfig.router_reads_block_input is True
    other = laguna.laguna_tiny()
    ids = paddle.to_tensor(np.zeros((1, 16), np.int32))
    other(ids)
    assert "moe_tokens_unserved" not in other.step_counters()
    assert other.model.tokens_unserved is None
    made = other.model.layers[1](Tensor(_rand((1, 16, 128), 8)))
    assert len(made) == 3


def test_tokens_unserved_is_a_count_by_hand():
    """16 of 64 experts held, six a token: the counter is the tokens whose
    six all lie outside ``[offset, offset + held)``; a uniform router leaves
    C(48, 6) / C(64, 6) = 16.4% of them so."""
    from math import comb

    paddle.seed(1)
    layer = DroplessMoELayer(32, 8, 64, 6, num_local_experts=16,
                             expert_offset=16, score_func="softmax",
                             body="reglu")
    x = Tensor(_rand((4096, 32), 9))
    out = np.asarray(layer(x)._data)
    idx = np.asarray(layer.expert_idx._data)
    nowhere = ~((idx >= 16) & (idx < 32)).any(axis=1)
    by_hand = int(np.sum(nowhere))
    assert int(layer.tokens_unserved()) == by_hand
    # such a token leaves with exactly nothing (a served one may too: a
    # ReLU gate of 8 units is all shut now and then)
    assert not out[nowhere].any() and out[~nowhere].any(axis=1).mean() > 0.9
    assert by_hand / 4096 == pytest.approx(comb(48, 6) / comb(64, 6),
                                           abs=0.03)


# ---------------------------------------------------- the kernels, group 7 --
@pytest.mark.parametrize("window", [None, 100])
def test_the_flash_kernels_at_a_group_of_7(monkeypatch, window):
    """28 q heads over 4 kv heads, the first group that is no power of two,
    in interpret mode with blocks of 64 over 256 positions, value and all
    three gradients against the REFERENCE's attention (a head and 2,048
    query rows at a time, its mask written out)."""
    monkeypatch.setattr(ak, "_blocks", lambda seq_q, seq_k: (64, 64))
    q, k, v = _rand((256, 28, 32), 1), _rand((256, 4, 32), 2), \
        _rand((256, 4, 32), 3)
    do = _rand((256, 28, 32), 4)

    def kernels(q, k, v):
        return ak.flash_attention_pallas(q[None], k[None], v[None],
                                         is_causal=True, interpret=True,
                                         window=window)[0]

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(kernels, q, k, v)
        got_grads = pull(do)
        want, pull = jax.vjp(lambda q, k, v: ref.attend(q, k, v, window),
                             q, k, v)
        want_grads = pull(do)
    for g, w, name in zip((got, *got_grads), (want, *want_grads),
                          ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    assert ak.supports(16384, 16384, 128, 128, 28, 4, window=4096)
    assert ak.supports(16384, 16384, 128, 128, 28, 4)
    assert ak.operand_layouts(128, 128, 7)[1] == {}
