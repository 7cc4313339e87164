"""An expert decoder trained by diffusion over blocks (``models/sdar.py`` on
the shell of ``models/moe_decoder.py``: the noised and the clean copy of a
row through one stack under the block-diffusion mask), at a size the CPU
runs, on seeded weights:

- the model against the plain reference ``chipbench/reference/sdar.py``
  (float32, ``highest``, independent of ``paddle_tpu``): logits, counters,
  loss, every leaf's gradient, two steps of AdamW through ``jit.TrainStep``;
- planted faults (a label shift, the ``1 / t`` weight left out, noised
  rows that see earlier noised blocks, the clean half's positions counted
  on, no q/k norm) each FAIL that comparison;
- the SHARE test: the 8 shares of 16 experts, no shared expert, top-8
  softmax, add up to the uncut reference's layer;
- the options of ``GroupedGatedAttention`` (no gate, q/k norm, position ids,
  the mask), the stated bfloat16 mix, the published preset.

Tolerances: the float32 program and the float32 reference differ by the
order of their sums alone.  Logits agree to 2e-4 of their size (a matmul's
reordering over 64 terms and two layers); the loss to 1e-5; a leaf's
gradient to 2e-3 of the leaf's largest entry (the weights ``1 / t`` reach
1,000, and a gradient is their sum).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import DroplessMoELayer
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models import laguna, moe_decoder, sdar
from paddle_tpu.models.sdar import SdarForBlockDiffusion

from chipbench.reference import sdar as ref
from chipbench.runners import laguna_train as grouped
from chipbench.runners import sdar_train as runner
from chipbench.tests.test_sdar_runner import plant
from chipbench.traffic_kinds import block_diffusion

# 4 q heads over 2 kv heads of 16, two layers, 16 router outputs, four a
# position, blocks of 4 in rows of 32 data tokens (64 positions)
BASE = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts_per_tok=4,
            norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
            rope_theta=1000000, rms_norm_eps=1e-6, vocab_size=96,
            block_length=4, mask_token_id=95)
SHARES = {"uncut": dict(num_experts=16, deployment={}),
          "share-8-of-16-from-4": dict(
              num_experts=8,
              deployment={"router_experts": 16, "expert_offset": 4})}
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}
TRAFFIC = {"batch": 2, "seq": 32, "block": 4, "t_low": 0.001, "t_high": 1.0}


def _seeded(share="share-8-of-16-from-4", seed=7, dtype=jnp.float32):
    """(model group, program model holding the reference's seeded weights,
    the reference's tree)."""
    m = runner.model_group({**BASE, **SHARES[share]})
    paddle.seed(0)
    model = SdarForBlockDiffusion(runner.model_config(m))
    if dtype != jnp.float32:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    tree = ref.init_params(seed, m, dtype)
    grouped.load_seeded(model, tree, ref, m)
    return m, model, tree


def _batch(step=0, seed=5, **traffic):
    """The cell's own traffic kind at the test's size: ``(ids, (noised,
    t))``."""
    return block_diffusion.generate({**TRAFFIC, **traffic}, seed, 0,
                                    BASE["vocab_size"])(step)


def _program_key(m, name):
    group, leaf, layer = grouped.program_key(
        name, lambda l: ref.group_of(m, l))
    return f"{group}.{leaf}", layer


def _leaf(tree, m, name):
    key, layer = _program_key(m, name)
    group, leaf = key.split(".", 1)
    return tree[group][leaf] if layer is None else tree[group][leaf][layer]


def _ref_loss(tree, batch, m):
    ids, (noised, t) = batch
    total = sum(ref.row_loss_sum(tree, jnp.asarray(ids[r]),
                                 jnp.asarray(noised[r]), jnp.asarray(t[r]),
                                 m)[0] for r in range(ids.shape[0]))
    return total / ids.size


def _program_loss_and_grads(model, batch):
    ids, (noised, t) = (jax.tree_util.tree_map(jnp.asarray, batch))
    params = {n: a._data for n, a in model.state_dict().items()}

    def loss(p):
        logits = functional_call(model, p, ids, noised)
        return model.loss(Tensor(logits), ids, noised, t)._data

    return jax.value_and_grad(loss)(params)


# ----------------------------------------- program against the reference --
@pytest.mark.parametrize("share", list(SHARES))
def test_logits_and_counters_match_the_reference(share):
    m, model, tree = _seeded(share)
    ids, (noised, _) = _batch()
    with jax.default_matmul_precision("highest"):
        got = model(paddle.to_tensor(ids), paddle.to_tensor(noised))._data
        counters = model.step_counters()
        want = [ref.forward_row(tree, jnp.asarray(ids[r]),
                                jnp.asarray(noised[r]), m)
                for r in range(ids.shape[0])]
    assert got.shape == (2, 32, BASE["vocab_size"])     # the noised half
    for r, (logits, _) in enumerate(want):
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(logits),
                                   rtol=2e-4, atol=2e-6)
    # every one of the 2 L positions is routed
    counts = np.asarray(counters["moe_tokens_per_expert"])
    assert counts.shape == (2, m["num_experts"])
    np.testing.assert_array_equal(counts, sum(np.asarray(c)
                                              for _, c in want))
    if share == "uncut":
        assert counts.sum() == 2 * 2 * 64 * 4       # layers, rows, 2 L, k
    assert int(counters["blockdiff_masked_tokens"]) \
        == int((noised == BASE["mask_token_id"]).sum())
    # off the TPU the composition scores the dense square
    assert np.asarray(counters["blockdiff_pairs_scored"]).tolist() \
        == [64 * 64] * 2
    assert np.asarray(counters["blockdiff_pairs_needed"]).tolist() \
        == [32 * (32 + 4)] * 2


@pytest.mark.parametrize("share", list(SHARES))
def test_loss_and_every_leafs_gradient_match_the_reference(share):
    m, model, tree = _seeded(share)
    batch = _batch(1)
    with jax.default_matmul_precision("highest"):
        got_loss, got = _program_loss_and_grads(model, batch)
        want_loss, want = jax.value_and_grad(_ref_loss)(tree, batch, m)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name in model.state_dict():
        w = np.asarray(_leaf(want, m, name))
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)


def test_two_steps_of_adamw_follow_the_reference():
    """Float32 all through (no amp), the step object the cell times, fed
    by the cell's own traffic kind: losses, loss terms, and where every
    leaf stands after two steps."""
    m, model, _ = _seeded()
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, *lb: model.loss(lg, *lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    batches = [_batch(s) for s in (3, 4)]
    with jax.default_matmul_precision("highest"):
        losses, masked = [], []
        for ids, (noised, t) in batches:
            a, b, c = (paddle.to_tensor(x) for x in (ids, noised, t))
            losses.append(float(step((a, b), (a, b, c))))
            masked.append(int(step.counters["blockdiff_masked_tokens"]))
        counts = np.asarray(step.counters["moe_tokens_per_expert"])
        params = {_program_key(m, n): np.asarray(a)
                  for n, a in step.state_dict()["params"].items()}
        want = ref.train_reference(7, m, batches, HP, jnp.float32,
                                   against=params)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    assert masked == want["masked_tokens"]
    np.testing.assert_array_equal(counts, want["expert_counts"][1])
    assert min(want["param_change_cosines"].values()) > 0.999
    for key, a in want["params"].items():
        np.testing.assert_allclose(params[key], np.asarray(a), rtol=1e-3,
                                   atol=2e-5, err_msg=str(key))
    norms = ref.change_norms(7, m, jnp.float32, params)
    for key, w in want["param_change_norms"].items():
        assert norms[key] == pytest.approx(w, rel=2e-3), key


def test_remat_by_block_changes_no_value():
    ids, (noised, t) = _batch(5)
    a, b, c = (paddle.to_tensor(x) for x in (ids, noised, t))
    seen = []
    for remat in (False, ["flash_attention_out", "flash_attention_lse"]):
        _, model, _ = _seeded("uncut")
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, *lb: model.loss(lg, *lb), opt,
                         remat=remat)
        loss = float(step((a, b), (a, b, c)))
        seen.append((loss, {n: np.asarray(p) for n, p in
                            step.state_dict()["params"].items()}))
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-6)
    for name, p in seen[0][1].items():
        np.testing.assert_allclose(p, seen[1][1][name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)


def test_the_stated_bfloat16_mix_stays_near_the_reference():
    """``amp.decorate`` O2: parameters, matmul operands, the residual stream
    and the gradients in bfloat16 (a relative step of 2 ** -8 = 0.4%); the
    norms' and the q/k norms' statistics, the softmax, the router, the cross
    entropy and the ``1 / t`` weights in float32.  The band: the loss within
    0.5% of the float32 reference's ON THE SAME bfloat16 weights, the loss
    terms exact, every leaf's gradient within 8% of its norm and pointing
    its way (cosine over 0.99)."""
    m, model, tree = _seeded(dtype=jnp.bfloat16)
    assert all(t._data.dtype == jnp.bfloat16
               for t in model.state_dict().values())
    batch = _batch(8)
    loss, grads = _program_loss_and_grads(model, batch)
    tree32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(_ref_loss)(tree32, batch, m)
    assert loss.dtype == jnp.float32
    assert float(loss) == pytest.approx(float(want), rel=5e-3)
    for name, g in grads.items():
        assert g.dtype == jnp.bfloat16, name
        g = np.asarray(g.astype(jnp.float32)).ravel()
        w = np.asarray(_leaf(want_grads, m, name)).ravel()
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.08, name
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.99, name


def test_the_seeding_keys_draw_what_the_configuration_says():
    """``embedding_range``, ``mask_row`` and ``qk_norm_gain`` change how the
    reference draws the embedding and the q/k gains (the program loads that
    tree); without them every leaf is drawn as the other families'."""
    plain = ref.init_params(3, runner.model_group(
        {**BASE, **SHARES["uncut"]}), jnp.float32)
    m = runner.model_group({**BASE, **SHARES["uncut"], "embedding_range": 1.0,
                            "mask_row": "mean", "qk_norm_gain": 1.6})
    tree = ref.init_params(3, m, jnp.float32)
    embed, was = (np.asarray(t["embed"]["weight"]) for t in (tree, plain))
    assert was.std() == pytest.approx(0.02, rel=0.05)
    np.testing.assert_allclose(embed[:-1], was[:-1] / 0.02, rtol=1e-6)
    np.testing.assert_allclose(embed[-1], embed[:-1].mean(axis=0),
                               rtol=1e-5, atol=1e-7)
    for leaf in ("attn.q_norm.weight", "attn.k_norm.weight"):
        np.testing.assert_allclose(np.asarray(tree["blocks"][leaf]),
                                   np.asarray(plain["blocks"][leaf]) + 0.6,
                                   rtol=1e-6)
        assert abs(float(np.mean(plain["blocks"][leaf])) - 1.0) < 0.02
    for leaf, a in plain["blocks"].items():
        if "_norm" not in leaf:
            np.testing.assert_array_equal(np.asarray(tree["blocks"][leaf]),
                                          np.asarray(a), err_msg=leaf)
    # and the program follows the reference under them as without
    _, model, _ = _seeded("uncut")
    grouped.load_seeded(model, tree, ref, m)
    ids, (noised, _) = _batch(2)
    with jax.default_matmul_precision("highest"):
        got = model(paddle.to_tensor(ids), paddle.to_tensor(noised))._data
        want = ref.forward_row(tree, jnp.asarray(ids[0]),
                               jnp.asarray(noised[0]), m)[0]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------------ planted faults must show --
def _fault(monkeypatch, fault):
    """Break the PROGRAM (the reference stays sound)."""
    real = SdarForBlockDiffusion.loss
    if fault == "labels_shifted":
        # position i held to x0_{i + 1}, as a next-token loss would
        monkeypatch.setattr(
            SdarForBlockDiffusion, "loss",
            lambda self, logits, ids, noised, t: real(
                self, logits, jnp.roll(sdar._data(ids), -1, axis=1), noised,
                t))
    elif fault == "no_qk_norm":
        monkeypatch.setattr(laguna, "_head_norm", lambda x, w, eps: x)
    else:
        # the three the benchmark plants in its timed program too
        plant(monkeypatch, fault)


@pytest.mark.parametrize("fault", [
    "labels_shifted", "no_t_weight", "noised_sees_earlier_noised",
    "clean_positions_count_on", "no_qk_norm"])
def test_a_planted_fault_fails_the_comparison(monkeypatch, fault):
    """Each fault moves the loss AND a gradient far outside the agreement
    the sound program reaches (1e-5 of the loss, 2e-3 of a gradient)."""
    m, model, tree = _seeded(seed=17)
    batch = _batch(9)
    _fault(monkeypatch, fault)
    with jax.default_matmul_precision("highest"):
        loss, grads = _program_loss_and_grads(model, batch)
        want, want_grads = jax.value_and_grad(_ref_loss)(tree, batch, m)
    loss_off = abs(float(loss) - float(want)) / float(want)
    grad_off = max(
        float(np.linalg.norm(np.asarray(g) - np.asarray(_leaf(want_grads, m,
                                                              n)))
              / np.linalg.norm(np.asarray(_leaf(want_grads, m, n))))
        for n, g in grads.items())
    # read: the loss off by 5.7e-4 (the mask, at seeded weights whose
    # attention is all but uniform) to 0.31, a gradient by 0.54 to 1.9
    assert loss_off > 1e-4, loss_off
    assert grad_off > 0.05, grad_off


# ------------------------------------------------------------- the share --
def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """8 chips x 2 experts of a 16-expert router, no shared expert, top-8
    softmax normed over the chosen: the routed parts the shares'
    ``DroplessMoELayer``s give are the uncut REFERENCE layer (``sum_i w_i
    E_i(b)`` over all sixteen), and the shares' counts are its counts, side
    by side."""
    base = {**BASE, "num_experts_per_tok": 8}
    uncut = runner.model_group({**base, "num_experts": 16, "deployment": {}})

    def layer_params(m):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32),
            ref.layer_params(ref.seed_key(3), 1, m, jnp.float32))

    p_all = layer_params(uncut)
    b = jnp.asarray(np.random.RandomState(1).randn(40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_counts = ref.expert_ffn(b, p_all, uncut)
        total, counts = 0.0, []
        for chip in range(8):
            m = runner.model_group({
                **base, "num_experts": 2,
                "deployment": {"router_experts": 16,
                               "expert_offset": 2 * chip}})
            p = layer_params(m)
            # an expert's weights are drawn from its GLOBAL index
            np.testing.assert_array_equal(
                np.asarray(p["moe.experts.down"]),
                np.asarray(p_all["moe.experts.down"][2 * chip:2 * chip + 2]))
            layer = DroplessMoELayer(
                64, 32, 16, 8, num_shared_experts=0, num_local_experts=2,
                expert_offset=2 * chip, score_func="softmax")
            assert layer.shared_experts is None
            layer.set_state_dict({
                "router.weight": Tensor(p["moe.router.weight"]),
                "experts.gate_up": Tensor(p["moe.experts.gate_up"]),
                "experts.down": Tensor(p["moe.experts.down"])})
            part = layer(Tensor(b))._data
            total = total + part
            counts.append(np.asarray(layer.tokens_per_expert))
            # and the reference's own share says the same
            ref_part, c = ref.expert_ffn(b, p, m)
            np.testing.assert_allclose(np.asarray(ref_part),
                                       np.asarray(part), rtol=1e-4,
                                       atol=1e-6)
            np.testing.assert_array_equal(np.asarray(c), counts[-1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(want_counts))
    assert int(np.sum(want_counts)) == 40 * 8      # nothing dropped


# ---------------------------------------------- the attention's options --
def _attention(**options):
    paddle.seed(1)
    return laguna.GroupedGatedAttention(
        32, 4, 2, 8, {"rope_theta": 10000}, 0.05, 0.05, **options)


def test_the_options_are_off_by_default_and_name_their_parameters():
    plain = _attention()
    assert [n for n, _ in plain.named_parameters()] == [
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "g_proj.weight",
        "o_proj.weight"]
    assert (plain.window, plain.block_diffusion, plain.q_norm) \
        == (None, None, None)
    ours = _attention(gate=False, qk_norm_eps=1e-6, block_diffusion=4)
    assert [n for n, _ in ours.named_parameters()] == [
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight",
        "q_norm.weight", "k_norm.weight"]
    assert ours.q_norm.weight.shape == [8]          # one gain a layer
    with pytest.raises(ValueError, match="exclude"):
        _attention(window=4, block_diffusion=4)


def test_position_ids_default_to_a_count_and_one_code_path():
    attn = _attention()
    cos, sin = attn.rope(6)
    again, _ = attn.rope(6, np.arange(6))
    np.testing.assert_array_equal(cos, again)
    twice, _ = attn.rope(6, np.tile(np.arange(3), 2))
    np.testing.assert_array_equal(twice[:3], cos[:3])
    np.testing.assert_array_equal(twice[3:], cos[:3])
    # the shared helper, as the other families call it
    np.testing.assert_array_equal(
        laguna.rope_tables(8, 6, {"rope_theta": 10000})[0], cos)


def test_the_q_k_norm_is_a_float32_rms_norm_a_head_under_one_gain():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 3, 8), jnp.bfloat16)
    g = jnp.asarray(1.0 + 0.1 * np.random.RandomState(1).randn(8),
                    jnp.bfloat16)
    got = laguna._head_norm(Tensor(x), Tensor(g), 1e-6)._data
    a = np.asarray(x.astype(jnp.float32))
    want = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(g.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               rtol=2 ** -8)


def test_a_row_of_odd_blocks_or_two_shapes_is_refused():
    model = sdar.sdar_tiny()
    ids = paddle.to_tensor(np.zeros((1, 30), np.int32))
    with pytest.raises(ValueError, match="whole blocks"):
        model(ids, ids)
    with pytest.raises(ValueError, match="one shape"):
        model(paddle.to_tensor(np.zeros((1, 32), np.int32)), ids)


def test_a_dense_layer_is_refused():
    for odd in (dict(mlp_only_layers=[1]), dict(decoder_sparse_step=2)):
        with pytest.raises(NotImplementedError, match="dense layer"):
            sdar.sdar_tiny(num_hidden_layers=4, **odd)


def test_it_is_the_one_shell_and_the_one_grouped_attention():
    model = sdar.sdar_tiny()
    assert issubclass(SdarForBlockDiffusion,
                      moe_decoder.MoeDecoderForCausalLM)
    assert not [n for n in vars(sdar)
                if n.endswith(("DecoderLayer", "Model"))]
    assert [n for n in vars(sdar) if n.endswith("Attention")] \
        == ["GroupedGatedAttention"]             # laguna's, imported
    assert {type(l) for l in model.model.layers} \
        == {moe_decoder.MoeDecoderLayer}
    assert {type(l.attn) for l in model.model.layers} \
        == {laguna.GroupedGatedAttention}
    assert all(l.moe.shared_experts is None for l in model.model.layers)


def test_train_step_hands_back_the_counters_and_the_loss_falls():
    paddle.seed(0)
    model = sdar.sdar_tiny(num_local_experts=8, expert_offset=4)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=3e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, *lb: model.loss(lg, *lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    # ONE batch again and again: fresh noise moves the loss more than a
    # step of learning does
    ids, (noised, t) = block_diffusion.generate(
        {**TRAFFIC, "seq": 64}, 3, 0, 512)(0)
    a, b, c = (paddle.to_tensor(x) for x in (ids, noised, t))
    losses = [float(step((a, b), (a, b, c))) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.5, losses
    counters = step.counters
    assert set(counters) == {
        "moe_tokens_per_expert", "moe_rows_buffered",
        "blockdiff_masked_tokens", "blockdiff_pairs_scored",
        "blockdiff_pairs_needed"}
    assert counters["moe_tokens_per_expert"].shape == (2, 8)
    assert int(counters["blockdiff_masked_tokens"]) \
        == int((noised == 511).sum())


def test_preset_is_the_published_model():
    cfg = sdar.SdarConfig(
        vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8)
    assert (cfg.mask_token_id, cfg.block_length, cfg.num_local_experts) \
        == (151935, 4, 128)
    model = sdar.sdar_30b_a3b(num_hidden_layers=1, num_local_experts=2,
                              vocab_size=64)
    c = model.config
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.rope_theta, c.rms_norm_eps) \
        == (2048, 32, 4, 128, 768, 128, 8, 1000000, 1e-6)
    sizes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    layer = "model.layers.0."
    assert sizes[layer + "attn.q_proj.weight"] == (2048, 4096)
    assert sizes[layer + "attn.k_proj.weight"] == (2048, 512)
    assert sizes[layer + "attn.q_norm.weight"] == (128,)
    assert sizes[layer + "moe.router.weight"] == (2048, 128)
    assert sizes[layer + "moe.experts.gate_up"] == (2, 2048, 1536)
    # a layer with all 16 of the cell's experts: ISSUE 44's count
    held = sum(int(np.prod(s)) for n, s in sizes.items()
               if n.startswith(layer) and ".experts." not in n)
    assert held + 16 * 3 * 2048 * 768 == 94638336
