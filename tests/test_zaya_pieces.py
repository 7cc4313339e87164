"""``models/zaya.py`` piece by piece, at a size the CPU runs (the model
against the reference as a whole: ``tests/test_zaya.py``, whose seeded
models and helpers this file borrows):

- each fault the benchmark plants moves a layer's output, and the sound
  layer does not;
- compressed convolutional attention's pieces one by one (causality of every
  piece, the two convolutions as sums over their taps, the value shift, the
  q-k mean over groups, the norm's ``sqrt(D)`` and its temperature);
- the router: the top-1 weight ``p[e*]``, its gradient, the selection bias;
- the SHARE test: the two shares of four experts add up to the uncut
  reference's layer;
- the shell's three options, the published preset.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import DroplessMoELayer
from paddle_tpu.incubate.distributed.models.moe import dropless, moe_layer
from paddle_tpu.jit import functional_call
from paddle_tpu.models import moe_decoder, zaya
from paddle_tpu.models.zaya import ZayaForCausalLM
from paddle_tpu.ops.registry import raw

from chipbench.reference import zaya as ref
from chipbench.runners import zaya_train as runner
from chipbench.tests.test_zaya_runner import FAULTS, plant

from test_zaya import BASE, _seeded


# ------------------------------------------------ planted faults must show --
@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_one_layer_matches_the_reference_and_a_planted_fault_does_not(
        monkeypatch, fault):
    """A layer's attention, its experts' part and its router state against
    the reference's on a normed row and a router state of unit size: the
    sound program agrees to 1e-4, each fault the benchmark plants moves one
    of the three by more than a hundredth of its size.  (End to end, by the
    limit that holds each: ``chipbench/tests/test_zaya_runner.py``.)"""
    m, model, tree = _seeded("uncut", seed=17)
    r = np.random.RandomState(9)
    h = jnp.asarray(r.randn(1, 24, 64), jnp.float32)
    state = jnp.asarray(r.randn(1, 24, 8), jnp.float32)
    p = {n: a[1] for n, a in tree["blocks"].items()}
    layer = model.model.layers[1]
    if fault is not None:
        plant(monkeypatch, fault)
    with jax.default_matmul_precision("highest"):
        got = [layer.attn(Tensor(h)),
               layer.moe(Tensor(h), router_state=Tensor(state)),
               layer.moe.router_state_out]
        made, want_r, _ = ref.expert_ffn(h[0], state[0], p, m)
        want = [ref.cca(h[0], p, m), made, want_r]
    off = max(float(jnp.abs(g._data[0] - w).max() / jnp.abs(w).max())
              for g, w in zip(got, want))
    assert off < 1e-4 if fault is None else off > 1e-2, (fault, off)


# --------------------------------------------- attention's pieces, one by one
def _attention(seed=1):
    paddle.seed(seed)
    return zaya.CompressedConvAttention(
        32, 4, 2, 8, (2, 2), {"rope_theta": 5000000,
                              "partial_rotary_factor": 0.5}, 0.3, 0.3, 1e-5)


def _pieces():
    rng = np.random.RandomState(0)
    attn = _attention()
    w0 = jnp.asarray(rng.randn(6, 2), jnp.float32)
    w1 = jnp.asarray(rng.randn(3, 2, 4, 4), jnp.float32)
    return {
        "time_shift": (lambda x: raw("time_shift")(x), (2, 9, 3, 4)),
        "causal_conv1d_k2": (lambda x: raw("causal_conv1d")(x, w0),
                             (2, 9, 6)),
        "causal_conv1d_heads": (
            lambda x: raw("causal_conv1d_heads")(x, w1), (2, 9, 3, 4)),
        "attention": (lambda x: attn(Tensor(x))._data, (2, 9, 32)),
    }


@pytest.mark.parametrize("piece", ["time_shift", "causal_conv1d_k2",
                                   "causal_conv1d_heads", "attention"])
def test_a_change_at_position_t_reaches_no_output_before_t(piece):
    f, shape = _pieces()[piece]
    x = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
    moved = x.at[:, 5:].add(1.0)
    got, base = np.asarray(f(moved)), np.asarray(f(x))
    # bit for bit but for the attention's softmax over the whole row
    np.testing.assert_allclose(got[:, :5], base[:, :5], rtol=0,
                               atol=2e-6 if piece == "attention" else 0)
    assert np.abs(got[:, 5:] - base[:, 5:]).max() > 1e-3


def test_the_two_convolutions_are_sums_over_their_taps():
    r = np.random.RandomState(2)
    x = r.randn(2, 7, 3, 4).astype(np.float32)
    w0 = r.randn(12, 2).astype(np.float32)
    w1 = r.randn(3, 2, 4, 4).astype(np.float32)
    flat = x.reshape(2, 7, 12)
    want0 = np.zeros_like(flat)
    want1 = np.zeros_like(x)
    for t in range(7):
        want0[:, t] = w0[:, 1] * flat[:, t]
        want1[:, t] = np.einsum("bnd,nde->bne", x[:, t], w1[:, 1])
        if t:
            want0[:, t] += w0[:, 0] * flat[:, t - 1]
            want1[:, t] += np.einsum("bnd,nde->bne", x[:, t - 1], w1[:, 0])
    np.testing.assert_allclose(raw("causal_conv1d")(flat, w0), want0,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(raw("causal_conv1d_heads")(x, w1), want1,
                               rtol=1e-5, atol=1e-6)
    # none across heads: head 2's input reaches head 2's output alone
    moved = x.copy()
    moved[:, :, 2] += 1.0
    got = np.asarray(raw("causal_conv1d_heads")(moved, w1))
    np.testing.assert_array_equal(got[:, :, :2], want1[:, :, :2].astype(
        got.dtype) * 0 + np.asarray(raw("causal_conv1d_heads")(x, w1))[
            :, :, :2])
    # bfloat16 operands, one rounding
    low = raw("causal_conv1d_heads")(jnp.asarray(x, jnp.bfloat16),
                                     jnp.asarray(w1, jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low.astype(jnp.float32)), want1,
                               rtol=0.05, atol=0.1)


def test_the_later_value_heads_read_the_previous_token():
    """kv head 1's value at position ``t`` is ``h_{t-1} W_vb``: zero at
    ``t = 0``; head 0 reads the position itself."""
    attn = _attention()
    h = Tensor(jnp.asarray(np.random.RandomState(3).randn(1, 6, 32),
                           jnp.float32))
    plain = attn.v_proj(h).reshape([1, 6, 2, 8])
    q = attn.q_proj(h).reshape([1, 6, 4, 8])
    k = attn.k_proj(h).reshape([1, 6, 2, 8])
    _, _, v = attn.mix(q, k, plain)
    v, plain = np.asarray(v._data), np.asarray(plain._data)
    np.testing.assert_array_equal(v[:, :, 0], plain[:, :, 0])
    np.testing.assert_array_equal(v[:, 0, 1], np.zeros_like(v[:, 0, 1]))
    np.testing.assert_array_equal(v[:, 1:, 1], plain[:, :-1, 1])


def test_the_q_k_mean_is_over_groups_and_the_norm_has_sqrt_d_and_tau():
    r = np.random.RandomState(4)
    q_conv, q_lat = (r.randn(1, 5, 4, 8).astype(np.float32) for _ in "ab")
    k_conv, k_lat = (r.randn(1, 5, 2, 8).astype(np.float32) for _ in "ab")
    tau = np.array([0.5, 2.0], np.float32)
    q, k = raw("cca_qk_mean_norm")(q_conv, k_conv, q_lat, k_lat, tau, 0.0)
    want_q = q_conv + 0.5 * (q_lat + np.repeat(k_lat, 2, axis=2))
    want_k = k_conv + 0.5 * (
        q_lat.reshape(1, 5, 2, 2, 8).mean(axis=3) + k_lat)
    unit = lambda x: np.sqrt(8) * x / np.linalg.norm(      # noqa: E731
        x, axis=-1, keepdims=True)
    np.testing.assert_allclose(q, unit(want_q), rtol=1e-5)
    np.testing.assert_allclose(k, unit(want_k) * tau[:, None], rtol=1e-5)
    # every q vector has length sqrt(D), a k vector tau_j sqrt(D)
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), np.sqrt(8),
                               rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(k, axis=-1)[0, 0],
                               tau * np.sqrt(8), rtol=1e-5)


# ------------------------------------------------------------- the router --
def _router(top_k=1, **kw):
    paddle.seed(2)
    return moe_layer.StateMlpRouter(16, 4, top_k, 8, init_std=0.5, **kw)


def test_the_top_1_weight_is_the_probability_and_the_router_learns():
    router = _router()
    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(12, 16), jnp.float32)
    state = jnp.asarray(r.randn(12, 8), jnp.float32)
    idx, w, made = router(Tensor(x), Tensor(state))
    p = {n: t._data for n, t in router.state_dict().items()}
    want_r = x @ p["down.weight"] + p["state_gain"] * state
    np.testing.assert_allclose(made._data, want_r, rtol=1e-5, atol=1e-6)
    a = want_r / np.sqrt(np.mean(np.square(want_r), -1, keepdims=True)
                         + 1e-5) * p["norm_weight"]
    for i in (1, 2):
        a = jax.nn.gelu(a @ p[f"fc{i}_weight"] + p[f"fc{i}_bias"],
                        approximate=False)
    prob = jax.nn.softmax(a @ p["fc3_weight"] + p["fc3_bias"], axis=-1)
    np.testing.assert_array_equal(idx._data[:, 0], np.argmax(prob, axis=-1))
    np.testing.assert_allclose(w._data[:, 0], np.max(prob, axis=-1),
                               rtol=1e-5)
    assert float(np.max(w._data)) < 1.0         # NOT renormalised to 1

    def weight_sum(params, state):
        out = functional_call(router, {**params, "e_score_correction_bias":
                                       p["e_score_correction_bias"]},
                              x, state)
        return jnp.sum(out[1])

    params = {n: a for n, a in p.items() if n != "e_score_correction_bias"}
    grads, d_state = jax.grad(weight_sum, argnums=(0, 1))(params, state)
    for name, g in grads.items():
        assert float(jnp.abs(g).max()) > 0, name
    # and through r_prev into the router of the layer before
    assert float(jnp.abs(d_state).max()) > 0
    # normed over the chosen one, the weight is the constant 1: no gradient
    normed = _router(norm_topk_prob=True)
    _, w1, _ = normed(Tensor(x), Tensor(state))
    np.testing.assert_allclose(w1._data, 1.0, rtol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2])
def test_a_selection_bias_steers_the_choice_and_not_the_weight(top_k):
    logits = jnp.asarray(np.random.RandomState(6).randn(10, 4), jnp.float32)
    prob = np.asarray(jax.nn.softmax(logits, axis=-1))
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    idx, w = dropless.route_softmax_topk(logits, top_k, 1.0, False, bias)
    assert (np.asarray(idx)[:, 0] == 2).all()
    np.testing.assert_allclose(
        w, np.take_along_axis(prob, np.asarray(idx), axis=1), rtol=1e-6)
    # without a bias: the function the softmax-routed families call
    plain_idx, plain_w = dropless.route_softmax_topk(logits, top_k, 1.0,
                                                     False)
    zero_idx, zero_w = dropless.route_softmax_topk(
        logits, top_k, 1.0, False, jnp.zeros(4))
    np.testing.assert_array_equal(plain_idx, zero_idx)
    np.testing.assert_allclose(plain_w, zero_w, rtol=1e-6)


# ------------------------------------------------------------- the share --
def test_the_two_shares_add_up_to_the_uncut_reference_layer():
    """2 chips x 2 experts of a 4-expert router, top-1, no shared expert:
    the routed parts the shares' ``DroplessMoELayer``s give add up to the
    uncut REFERENCE layer (``w E_{e*}(b)`` over all four), the router state
    (which every chip computes alike) counted once, and the shares' counts
    are its counts, side by side."""
    uncut = runner.model_group({**BASE, "num_experts": 4, "deployment": {}})

    def layer_params(m):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32),
            ref.layer_params(ref.seed_key(3), 1, m, jnp.float32))

    p_all = layer_params(uncut)
    r = np.random.RandomState(1)
    b = jnp.asarray(r.randn(40, 64), jnp.float32)
    state = jnp.asarray(r.randn(40, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_r, want_counts = ref.expert_ffn(b, state, p_all, uncut)
        total, counts = 0.0, []
        for chip in range(2):
            m = runner.model_group({
                **BASE, "num_experts": 2,
                "deployment": {"router_experts": 4,
                               "expert_offset": 2 * chip}})
            p = layer_params(m)
            # an expert's weights are drawn from its GLOBAL index
            np.testing.assert_array_equal(
                np.asarray(p["moe.experts.down"]),
                np.asarray(p_all["moe.experts.down"][2 * chip:2 * chip + 2]))
            layer = DroplessMoELayer(
                64, 32, 4, 1, norm_topk_prob=False, num_local_experts=2,
                expert_offset=2 * chip, score_func="softmax",
                router_state={"state_size": 8})
            missing, unexpected = layer.set_state_dict(
                {n[len("moe."):]: Tensor(a) for n, a in p.items()
                 if n.startswith("moe.")})
            assert not unexpected and all(
                n.endswith("e_score_correction_bias") for n in missing)
            part = layer(Tensor(b), Tensor(state))._data
            total = total + part
            counts.append(np.asarray(layer.tokens_per_expert))
            # every chip makes the same state
            np.testing.assert_allclose(
                np.asarray(layer.router_state_out._data),
                np.asarray(want_r), rtol=1e-5, atol=1e-6)
            # and the reference's own share says the same
            ref_part, _, c = ref.expert_ffn(b, state, p, m)
            np.testing.assert_allclose(np.asarray(ref_part),
                                       np.asarray(part), rtol=1e-4,
                                       atol=1e-6)
            np.testing.assert_array_equal(np.asarray(c), counts[-1])
            # a token whose expert is held elsewhere gets nothing here
            elsewhere = np.asarray(jnp.all(part == 0, axis=1))
            assert elsewhere.sum() == 40 - counts[-1].sum()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.sum(want_counts)) == 40       # top-1, nothing dropped


# ------------------------------------------------- the shell's new options --
def test_the_shells_three_options_are_off_for_the_other_families():
    cfg = moe_decoder.MoeDecoderConfig
    assert (cfg.router_state_size, cfg.residual_scale,
            cfg.tie_word_embeddings) == (0, False, False)
    from paddle_tpu.models.laguna import laguna_tiny
    other = laguna_tiny()
    names = [n for n, _ in other.named_parameters()]
    assert "lm_head.weight" in names
    assert not any(".res_" in n or "router.fc" in n for n in names)
    assert other.model.layers[1].res_1 is None
    assert other.model.layers[1].moe.router_state_out is None


def test_the_head_is_the_embedding_unless_the_config_unties_it():
    _, model, _ = _seeded("uncut")
    assert model.lm_head is None
    assert not any(n.startswith("lm_head")
                   for n, _ in model.named_parameters())
    paddle.seed(0)
    untied = ZayaForCausalLM(zaya.ZayaConfig(tie_word_embeddings=False))
    assert "lm_head.weight" in [n for n, _ in untied.named_parameters()]


def test_a_scaled_residual_add_is_what_it_says():
    paddle.seed(3)
    res = moe_decoder.ResidualScale(8)
    r = np.random.RandomState(7)
    vectors = {n: r.randn(8).astype(np.float32)
               for n in ("skip_scale", "skip_bias", "out_scale", "out_bias")}
    assert [float(p._data[0]) for _, p in res.named_parameters()] \
        == [1.0, 0.0, 1.0, 0.0]
    res.set_state_dict({n: Tensor(jnp.asarray(a))
                        for n, a in vectors.items()})
    x, made = (r.randn(2, 3, 8).astype(np.float32) for _ in "ab")
    got = res(Tensor(jnp.asarray(x)), Tensor(jnp.asarray(made)))._data
    want = (vectors["skip_scale"] * x + vectors["skip_bias"]) \
        + (vectors["out_scale"] * made + vectors["out_bias"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    low = res(Tensor(jnp.asarray(x, jnp.bfloat16)),
              Tensor(jnp.asarray(made, jnp.bfloat16)))._data
    assert low.dtype == jnp.bfloat16


def test_the_published_preset_states_the_published_sizes():
    cfg = zaya.ZayaConfig(**{
        k: v for k, v in dict(
            vocab_size=262272, hidden_size=2048, num_hidden_layers=40,
            num_attention_heads=8, num_key_value_heads=2, head_dim=128,
            moe_intermediate_size=2048, num_experts=16,
            router_hidden_size=256).items()})
    attn = cfg.make_attention(0)
    assert attn.q_proj.weight.shape == [2048, 1024]     # HALF the hidden
    assert attn.k_proj.weight.shape == [2048, 256]
    assert attn.o_proj.weight.shape == [1024, 2048]
    assert attn.q_conv0.shape == [1024, 2]
    assert attn.k_conv1.shape == [2, 2, 128, 128]
    assert (cfg.router_state_size, cfg.num_experts_per_tok,
            cfg.norm_topk_prob) == (256, 1, False)
    with pytest.raises(ValueError, match="odd"):
        zaya.ZayaConfig(num_key_value_heads=1, num_attention_heads=4)


def test_the_seeding_keys_draw_what_the_configuration_says():
    """``embedding_range``, ``final_norm_gain``, ``router_norm_gain``,
    ``router_bias_range`` and ``router_mlp_orthogonal`` change how the
    reference draws the embedding, two gains and the router MLP (the program
    loads that tree); every other leaf is drawn as without them."""
    base = {**BASE, "num_experts": 4, "deployment": {}}
    base.pop("router_mlp_orthogonal")
    plain = ref.init_params(3, runner.model_group(base), jnp.float32)
    keys = {"embedding_range": 1.0, "final_norm_gain": 0.02,
            "router_norm_gain": 0.05, "router_bias_range": 0.0}
    tree = ref.init_params(3, runner.model_group({**base, **keys}),
                           jnp.float32)
    np.testing.assert_allclose(tree["embed"]["weight"],
                               np.asarray(plain["embed"]["weight"]) / 0.02,
                               rtol=1e-6)
    np.testing.assert_allclose(
        tree["head"]["ln_f.weight"],
        np.asarray(plain["head"]["ln_f.weight"]) - 0.98, atol=1e-6)
    moved = {"moe.router.norm_weight": lambda a: a - 0.95,
             "moe.router.fc1_bias": lambda a: a * 0,
             "moe.router.fc2_bias": lambda a: a * 0,
             "moe.router.fc3_bias": lambda a: a * 0}
    for leaf, a in plain["blocks"].items():
        want = moved.get(leaf, lambda a: a)(np.asarray(a))
        np.testing.assert_allclose(np.asarray(tree["blocks"][leaf]), want,
                                   rtol=1e-5, atol=1e-7, err_msg=leaf)
    assert abs(float(np.mean(plain["blocks"]["moe.router.state_gain"]))
               - 0.5) < 0.02
    # orthonormal columns times the gain: in every layer, every matrix
    orth = ref.init_params(3, runner.model_group(
        {**base, "router_mlp_orthogonal": 3.2}), jnp.float32)
    for i, width in ((1, 8), (2, 8), (3, 4)):
        w = np.asarray(orth["blocks"][f"moe.router.fc{i}_weight"])
        np.testing.assert_allclose(
            np.einsum("lij,lik->ljk", w, w),
            np.broadcast_to(3.2 ** 2 * np.eye(width), (3, width, width)),
            atol=1e-4)
