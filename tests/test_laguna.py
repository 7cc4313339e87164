"""Window and full attention over grouped KV heads with per-head gates and
softmax-routed experts (``models/laguna.py`` on the shell of
``models/moe_decoder.py``), at a size the CPU runs, on seeded weights:

- the model against the plain reference ``chipbench/reference/laguna.py``:
  logits, counters, loss, every leaf's gradient, two steps of AdamW through
  ``jit.TrainStep``;
- the YaRN table against hand-computed values;
- the SHARE test: the routed parts the four shares of a layer give (4 chips
  x 2 experts), plus the shared expert counted once, are what the uncut
  reference layer gives;
- the softmax router, the sorted buffers' exact bound, the one shell.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoELayer,
                                                        TopKRouter, dropless)
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models import laguna, mla_moe, moe_decoder
from paddle_tpu.models.laguna import (FULL, LAGUNA_ROPE, WINDOW,
                                      LagunaForCausalLM)

from chipbench.reference import laguna as ref
from chipbench.runners import laguna_train as runner

# layer 0 full + dense, then window, window, window, full with experts;
# 4 | 6 q heads over 2 kv heads of 16; a window of 8 in 32 tokens; 16
# router outputs, three a token
BASE = dict(hidden_size=64, num_hidden_layers=5, num_key_value_heads=2,
            head_dim=16, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            layer_types=[FULL, WINDOW, WINDOW, WINDOW, FULL],
            mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
            rope_parameters=LAGUNA_ROPE, intermediate_size=128,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_experts_per_tok=3, norm_topk_prob=True,
            moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6, vocab_size=96)
SHARES = {"uncut": dict(num_experts=16, deployment={}),
          "share-4-of-16-from-4": dict(
              num_experts=4,
              deployment={"router_experts": 16, "expert_offset": 4})}
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}


def _seeded(share, seed=7):
    """(model group, program model holding the reference's seeded float32
    weights, the reference's tree)."""
    m = runner.model_group({**BASE, **SHARES[share]})
    paddle.seed(0)
    model = LagunaForCausalLM(runner.model_config(m))
    tree = ref.init_params(seed, m, jnp.float32)
    runner.load_seeded(model, tree, ref, m)
    return m, model, tree


def _ids(seed=0, rows=2, seq=32):
    return np.random.RandomState(seed).randint(
        0, BASE["vocab_size"], (rows, seq)).astype("int32")


def _program_key(m, name):
    group, leaf, layer = runner.program_key(
        name, lambda l: ref.group_of(m, l))
    return f"{group}.{leaf}", layer


@pytest.mark.parametrize("share", list(SHARES))
def test_logits_and_counters_match_the_reference(share):
    m, model, tree = _seeded(share)
    ids = _ids()
    with jax.default_matmul_precision("highest"):
        got = model(paddle.to_tensor(ids))._data
        counts = np.asarray(model.model.tokens_per_expert)
        want = [ref.forward_row(tree, jnp.asarray(row), m) for row in ids]
    for r, (logits, _) in enumerate(want):
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(logits),
                                   rtol=2e-4, atol=2e-6)
    assert counts.shape == (4, m["num_experts"])
    np.testing.assert_array_equal(counts, sum(np.asarray(c)
                                              for _, c in want))


@pytest.fixture(params=["composition", "kernel"])
def run_sums(request, monkeypatch):
    """The expert layer's way back to its tokens by the path the CPU takes
    (``dropless._run_sums``) or with the ``moe_run_sum`` kernel forced, in
    interpret mode, a row block of 16 over the tiny model's whole width."""
    if request.param == "kernel":
        from paddle_tpu.ops import pallas as pk
        from paddle_tpu.ops.pallas.moe_run_sum_kernel import (
            moe_run_sum_pallas)

        monkeypatch.setattr(
            pk, "moe_run_sum",
            lambda rows, rem, weights=None, *, max_run: moe_run_sum_pallas(
                rows, rem, weights, max_run=max_run, interpret=True,
                block=(16, rows.shape[1])))
    return request.param


@pytest.mark.parametrize("share", list(SHARES))
def test_loss_and_every_leafs_gradient_match_the_reference(share, run_sums):
    m, model, tree = _seeded(share)
    ids = _ids(1)
    names = list(model.state_dict())
    params = {n: model.state_dict()[n]._data for n in names}

    def loss(p):
        logits = functional_call(model, p, jnp.asarray(ids))
        return model.loss(Tensor(logits), Tensor(jnp.asarray(ids)))._data

    def ref_loss(t):
        total = sum(ref._row_loss_sum(t, jnp.asarray(r), jnp.asarray(r), m,
                                      "float32")[0] for r in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.value_and_grad(loss)(params)
        want_loss, want = jax.value_and_grad(ref_loss)(tree)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    ids_of = ref.layer_ids(m)
    for name in names:
        key, layer = _program_key(m, name)
        group, leaf = key.split(".", 1)
        w = want[group][leaf]
        if layer is not None:
            w = w[ids_of[group].index(layer)]
        w = np.asarray(w)
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)


def test_two_steps_of_adamw_follow_the_reference():
    """Float32 all through (no amp), the step object the cell times:
    losses, and where every leaf stands after two steps."""
    m, model, _ = _seeded("share-4-of-16-from-4")
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    batches = [(_ids(s), _ids(s)) for s in (3, 4)]
    with jax.default_matmul_precision("highest"):
        losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for x, y in batches]
        counts = np.asarray(step.counters["moe_tokens_per_expert"])
        params = {_program_key(m, n): np.asarray(a)
                  for n, a in step.state_dict()["params"].items()}
        want = ref.train_reference(7, m, batches, HP, jnp.float32,
                                   against=params)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    np.testing.assert_array_equal(counts, want["expert_counts"][1])
    assert min(want["param_change_cosines"].values()) > 0.999
    for key, a in want["params"].items():
        np.testing.assert_allclose(params[key], np.asarray(a), rtol=1e-3,
                                   atol=2e-5, err_msg=str(key))
    norms = ref.change_norms(7, m, jnp.float32, params)
    for key, w in want["param_change_norms"].items():
        assert norms[key] == pytest.approx(w, rel=2e-3), key


def test_remat_by_block_changes_no_value():
    m, model, _ = _seeded("uncut")
    ids = _ids(5)

    def loss_and_grads(remat):
        from paddle_tpu.nn.layer_base import block_remat

        names = list(model.state_dict())
        params = {n: model.state_dict()[n]._data for n in names}

        def loss(p):
            with block_remat(remat):
                logits = functional_call(model, p, jnp.asarray(ids))
            return model.loss(Tensor(logits), Tensor(jnp.asarray(ids)))._data
        return jax.jit(jax.value_and_grad(loss))(params)

    (l0, g0), (l1, g1) = loss_and_grads(None), loss_and_grads(
        ["flash_attention_out", "flash_attention_lse"])
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for n in g0:
        np.testing.assert_allclose(np.asarray(g0[n]), np.asarray(g1[n]),
                                   rtol=1e-4, atol=1e-7, err_msg=n)


# ------------------------------------------------------------- rotary ----
def test_yarn_table_against_hand_computed_values():
    """rope_parameters.full_attention of the source: 64 rotary dims (half
    of 128), base 500,000, factor 128 over 8192 original positions,
    beta_fast 32, beta_slow 1.

    low  = floor(64 ln(8192 / (32 * 2 pi)) / (2 ln 500000))
         = floor(64 * 3.70726 / 26.24473) = floor(9.0405) = 9
    high = ceil(64 ln(8192 / (2 pi)) / (2 ln 500000))
         = ceil(64 * 7.17300 / 26.24473) = ceil(17.4919) = 18
    pair 0 (i < low) keeps f_0 = 1; pair 31 (i > high) is interpolated,
    500000 ** (-62 / 64) / 128; pair 12, a third of the way, blends
    f (1 - 2/3) / 128 + f * 2/3 with f = 500000 ** (-24 / 64)."""
    p = LAGUNA_ROPE[FULL]
    assert laguna.yarn_correction_range(64, 500000, 8192, 32, 1) == (9, 18)
    inv = laguna.yarn_inv_freq(64, 500000, 128, 8192, 32, 1)
    assert inv.shape == (32,)
    assert inv[0] == pytest.approx(1.0)
    assert inv[9] == pytest.approx(500000 ** (-18 / 64))
    assert inv[31] == pytest.approx(500000 ** (-62 / 64) / 128)
    assert inv[31] == pytest.approx(2.35458e-8, rel=1e-5)
    f12 = 500000 ** (-24 / 64)
    assert inv[12] == pytest.approx(f12 * (1 / 3) / 128 + f12 * (2 / 3))
    assert inv[18] == pytest.approx(500000 ** (-36 / 64) / 128)
    assert p["attention_factor"] == pytest.approx(0.1 * math.log(128) + 1)
    cos, sin, rot = laguna.rope_tables(128, 8192, p)
    assert rot == 64 and cos.shape == sin.shape == (8192, 32)
    assert cos[0, 0] == pytest.approx(1.4852030263919618)
    assert sin[1, 0] == pytest.approx(1.4852030263919618 * math.sin(1.0),
                                      rel=1e-6)
    assert cos[8191, 31] == pytest.approx(
        1.4852030263919618 * math.cos(8191 * inv[31]), rel=1e-6)
    # the reference computes its own table, and the same one
    ref_inv, low, high = ref.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0,
                                           1.0)
    assert (low, high) == (9, 18)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-12)
    # the window layers': plain, all 128 dims, base 10,000
    cos, sin, rot = laguna.rope_tables(128, 16, LAGUNA_ROPE[WINDOW])
    assert rot == 128 and cos.shape == (16, 64)
    assert cos[3, 1] == pytest.approx(math.cos(3 * 10000 ** (-2 / 128)),
                                      rel=1e-6)


def test_partial_rotary_rotates_the_leading_dims_only():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 2, 16), jnp.float32)
    cos, sin, rot = laguna.rope_tables(16, 5, LAGUNA_ROPE[FULL])
    got = laguna._rotate_half(x, jnp.asarray(cos), jnp.asarray(sin))
    assert rot == 8
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    t, i = 3, 2
    want = x[0, t, 1, i] * cos[t, i] - x[0, t, 1, 4 + i] * sin[t, i]
    assert float(got[0, t, 1, i]) == pytest.approx(float(want), rel=1e-6)
    want = x[0, t, 1, 4 + i] * cos[t, i] + x[0, t, 1, i] * sin[t, i]
    assert float(got[0, t, 1, 4 + i]) == pytest.approx(float(want), rel=1e-6)


@pytest.mark.parametrize("seq", [16, 13])
def test_the_gate_on_the_tile_view_is_the_plain_product(seq):
    """``_gate`` multiplies on the view ``[B, T / 8, 8, N, D]`` (where the
    flash kernels leave ``out``, that view is the array as it lies) and on
    the plain one where 8 does not divide the sequence: bit for bit the
    same numbers, and the same gradients."""
    rng = np.random.RandomState(0)
    out = jnp.asarray(rng.randn(2, seq, 3, 16), jnp.bfloat16)
    g = jnp.asarray(rng.randn(2, seq, 3), jnp.bfloat16)

    def plain(out, g):
        return out * jax.nn.sigmoid(g.astype(jnp.float32))[..., None] \
            .astype(out.dtype)

    def total(f):
        return lambda out, g: jnp.sum(f(out, g).astype(jnp.float32) ** 2)

    gate = lambda out, g: laguna._gate(Tensor(out), Tensor(g))._data  # noqa: E731
    np.testing.assert_array_equal(
        np.asarray(gate(out, g), np.float32),
        np.asarray(plain(out, g), np.float32))
    for got, want in zip(jax.grad(total(gate), argnums=(0, 1))(out, g),
                         jax.grad(total(plain), argnums=(0, 1))(out, g)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# ------------------------------------------------------- expert layer ----
def test_softmax_router_norms_over_the_chosen():
    logits = jnp.asarray(np.random.RandomState(0).randn(6, 16), jnp.float32)
    idx, w = dropless.route_softmax_topk(logits, 3, 2.5)
    s = np.asarray(jax.nn.softmax(logits, -1))
    for t in range(6):
        top = np.argsort(-s[t])[:3]
        assert set(np.asarray(idx[t]).tolist()) == set(top.tolist())
        np.testing.assert_allclose(
            sorted(np.asarray(w[t])), sorted(2.5 * s[t, top] / s[t, top].sum()),
            rtol=1e-6)
    _, w = dropless.route_softmax_topk(logits, 3, 1.0, norm_topk=False)
    assert float(jnp.sum(w)) < 6.0
    router = TopKRouter(8, 16, 3, 2.5, score_func="softmax")
    assert "e_score_correction_bias" not in router.state_dict()
    assert "e_score_correction_bias" in TopKRouter(8, 16, 3).state_dict()
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        TopKRouter(8, 16, 3, score_func="tanh")


@pytest.mark.parametrize("held,top_k,rows", [(2, 3, 2), (8, 10, 8),
                                             (16, 6, 6), (4, 4, 4)])
def test_sorted_buffers_hold_min_of_top_k_and_held_rows_a_token(held, top_k,
                                                                 rows):
    """A token's experts are distinct, so at most ``min(top_k, held)`` of
    its assignments are served here: the bound is exact, nothing is
    dropped, and the layer computes what a loop over the experts does."""
    tokens, experts, h, inter = 24, 16, 8, 4
    assert dropless.sorted_rows(tokens, top_k, held) == tokens * rows
    rng = np.random.RandomState(held)
    idx = jnp.asarray(np.stack([rng.permutation(experts)[:top_k]
                                for _ in range(tokens)]), jnp.int32)
    w = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
    x = jnp.asarray(rng.randn(tokens, h), jnp.float32)
    gate_up = jnp.asarray(rng.randn(held, h, 2 * inter), jnp.float32)
    down = jnp.asarray(rng.randn(held, inter, h), jnp.float32)
    offset = 3

    def layer(x, w, gate_up, down):
        order, inverse, counts = dropless.sort_by_expert(idx, offset, held)
        assert order.shape[0] == tokens * rows
        xs = dropless.dispatch(x, order, inverse, counts)
        ys = dropless.experts_mlp(xs, gate_up, down, counts)
        return dropless.combine(ys, w, order, inverse, counts), counts

    def loop(x, w, gate_up, down):
        out = jnp.zeros_like(x)
        for e in range(held):
            we = jnp.sum(jnp.where(idx == e + offset, w, 0.0), axis=1)
            gu = x @ gate_up[e]
            out += we[:, None] * (
                (jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) @ down[e])
        return out

    got, counts = layer(x, w, gate_up, down)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(loop(x, w, gate_up, down)),
                               rtol=1e-4, atol=1e-5)
    served = int(np.sum((np.asarray(idx) >= offset)
                        & (np.asarray(idx) < offset + held)))
    assert int(counts.sum()) == served
    args = (x, w, gate_up, down)
    g_got = jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2), (0, 1, 2, 3))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2), (0, 1, 2, 3))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def _ref_layer_params(m, seed=3):
    """Layer 1's (a window layer with experts) reference leaves under the
    model group ``m``."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.layer_params(ref.seed_key(seed), 1, "window_moe", m,
                         jnp.float32))


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """4 chips x 2 experts of an 8-expert router: the routed parts the
    shares' ``DroplessMoELayer``s give, plus the shared expert counted
    once, are the uncut REFERENCE layer (``sum_i w_i E_i(b) + S(b)`` over
    all eight), and the shares' counts are its counts, side by side."""
    base = {**BASE, "num_experts_per_tok": 3}
    uncut = runner.model_group({**base, "num_experts": 8, "deployment": {}})
    p_all = _ref_layer_params(uncut)
    b = jnp.asarray(np.random.RandomState(1).randn(40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_counts = ref.expert_ffn(b, p_all, uncut)
        shared = ref._swiglu(b, p_all["moe.shared_experts.gate_up.weight"],
                             p_all["moe.shared_experts.down.weight"],
                             "float32")
        total, counts = shared, []
        for chip in range(4):
            m = runner.model_group({
                **base, "num_experts": 2,
                "deployment": {"router_experts": 8,
                               "expert_offset": 2 * chip}})
            p = _ref_layer_params(m)
            # an expert's weights are drawn from its GLOBAL index
            np.testing.assert_array_equal(
                np.asarray(p["moe.experts.down"]),
                np.asarray(p_all["moe.experts.down"][2 * chip:2 * chip + 2]))
            layer = DroplessMoELayer(
                64, 32, 8, 3, num_shared_experts=0,
                routed_scaling_factor=2.5, num_local_experts=2,
                expert_offset=2 * chip, score_func="softmax")
            layer.set_state_dict({
                "router.weight": Tensor(p["moe.router.weight"]),
                "experts.gate_up": Tensor(p["moe.experts.gate_up"]),
                "experts.down": Tensor(p["moe.experts.down"])})
            total = total + layer(Tensor(b))._data
            counts.append(np.asarray(layer.tokens_per_expert))
            # and the reference's own share says the same
            part, c = ref.expert_ffn(b, p, m)
            np.testing.assert_allclose(
                np.asarray(part - shared),
                np.asarray(layer(Tensor(b))._data), rtol=1e-4, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(c), counts[-1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.sum(want_counts)) == 40 * 3      # nothing dropped


# -------------------------------------------------------------- shell ----
def test_both_expert_models_are_the_one_shell():
    assert issubclass(LagunaForCausalLM, moe_decoder.MoeDecoderForCausalLM)
    assert issubclass(mla_moe.MlaMoeForCausalLM,
                      moe_decoder.MoeDecoderForCausalLM)
    for mod in (laguna, mla_moe):
        assert not [n for n in vars(mod)
                    if n.endswith(("DecoderLayer", "Model"))], mod
    a, b = laguna.laguna_tiny(), mla_moe.mla_moe_tiny()
    for model in (a, b):
        assert type(model.model) is moe_decoder.MoeDecoderModel
        assert {type(l) for l in model.model.layers} \
            == {moe_decoder.MoeDecoderLayer}
    assert {type(l.attn) for l in a.model.layers} \
        == {laguna.GroupedGatedAttention}
    assert {type(l.attn) for l in b.model.layers} == {mla_moe.MLAttention}
    # a layer's kind is its window: a sliding layer has one, a full none
    kinds = [(l.attn.num_heads, l.attn.window, l.moe is not None)
             for l in a.model.layers]
    assert kinds == [(4, None, False), (6, 8, True), (6, 8, True),
                     (6, 8, True), (4, None, True)]


def test_train_step_hands_back_the_counters_and_the_loss_falls():
    paddle.seed(0)
    model = laguna.laguna_tiny(num_local_experts=4, expert_offset=2)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 1024, (2, 32)).astype("int32"))
    losses = [float(step(ids, ids)) for _ in range(4)]
    assert losses[-1] < losses[0]
    counts = step.counters["moe_tokens_per_expert"]
    assert counts.shape == (4, 4) and counts.dtype == jnp.int32
    assert 0 < int(counts.sum()) <= 4 * 64 * 3
    # 64 tokens: the worst case's rows are every layer's only bucket
    rows = step.counters["moe_rows_buffered"]
    assert rows.dtype == jnp.int32 and rows.tolist() == [
        dropless.sorted_rows(64, model.config.num_experts_per_tok, 4)] * 4


def test_preset_is_the_published_model_and_a_bad_config_is_refused():
    cfg = dict(num_hidden_layers=5, num_local_experts=8, vocab_size=12544)
    shapes = jax.eval_shape(lambda: {
        k: v._data for k, v in
        laguna.laguna_s_2_1(**cfg).state_dict().items()})
    paddle.seed(0)      # the traced build left a traced key in the stream
    assert sum(math.prod(s.shape) for s in shapes.values()) == 811_017_216
    assert shapes["model.layers.1.attn.q_proj.weight"].shape == (3072, 9216)
    assert shapes["model.layers.4.attn.q_proj.weight"].shape == (3072, 6144)
    assert shapes["model.layers.1.attn.k_proj.weight"].shape == (3072, 1024)
    assert shapes["model.layers.1.attn.g_proj.weight"].shape == (3072, 72)
    assert shapes["model.layers.1.moe.router.weight"].shape == (3072, 256)
    assert shapes["model.layers.1.moe.experts.gate_up"].shape \
        == (8, 3072, 2048)
    assert shapes["model.layers.0.mlp.down.weight"].shape == (12288, 3072)
    with pytest.raises(ValueError, match="entries for"):
        laguna.LagunaConfig(num_hidden_layers=4)
    with pytest.raises(NotImplementedError, match="per-head"):
        laguna.LagunaConfig(gating="per-channel")
    with pytest.raises(ValueError, match="q heads over"):
        laguna.laguna_tiny(num_attention_heads_per_layer=[4, 5, 6, 6, 4])
