"""An expert decoder whose attention lives in a compressed, convolved latent
and whose top-1 router is an MLP over a state carried from layer to layer
(``models/zaya.py`` on the shell of ``models/moe_decoder.py``), at a size the
CPU runs, on seeded weights, against the plain reference
``chipbench/reference/zaya.py`` (float32, ``highest``, independent of
``paddle_tpu``): counters, loss, every leaf's gradient, two steps of AdamW
through ``jit.TrainStep``, the router's state through three layers with and
without rematerialisation, and the stated bfloat16 mix.  The pieces one by
one, the planted faults, the share test and the shell's options are in
``tests/test_zaya_pieces.py`` (a file of its own so that the suite's
workers share the load).

Tolerances: the float32 program and the float32 reference differ by the
order of their sums alone: the loss to 1e-5, a leaf's gradient to 2e-3 of
the leaf's largest entry.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models.zaya import ZayaForCausalLM

from chipbench.reference import zaya as ref
from chipbench.runners import laguna_train as grouped
from chipbench.runners import nemotron_h_train as with_bias
from chipbench.runners import zaya_train as runner

# 4 q heads over 2 kv heads of 16 (attention at half the hidden width),
# three layers, a 4-wide router through an 8-wide MLP, rows of 48
BASE = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, cca_time0=2, cca_time1=2,
            partial_rotary_factor=0.5,
            rope_parameters={"hybrid": {"rope_theta": 5000000}},
            moe_intermediate_size=32, num_experts_per_tok=1,
            router_hidden_size=8, rms_norm_eps=1e-5,
            tie_word_embeddings=True, vocab_size=96,
            router_mlp_orthogonal=1.0)
SHARES = {"uncut": dict(num_experts=4, deployment={}),
          "share-2-of-4-from-2": dict(
              num_experts=2,
              deployment={"router_experts": 4, "expert_offset": 2})}
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}
REMAT = ["flash_attention_out", "flash_attention_lse"]


def _seeded(share="share-2-of-4-from-2", seed=7, dtype=jnp.float32):
    """(model group, program model holding the reference's seeded weights,
    the reference's tree)."""
    m = runner.model_group({**BASE, **SHARES[share]})
    paddle.seed(0)
    model = ZayaForCausalLM(runner.model_config(m))
    if dtype != jnp.float32:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    tree = ref.init_params(seed, m, dtype)
    with_bias.load_seeded(model, tree, ref, m)
    return m, model, tree


def _batch(step=0, rows=2, seq=48):
    return np.random.default_rng(100 + step).integers(
        0, BASE["vocab_size"], (rows, seq)).astype(np.int32)


def _program_key(m, name):
    group, leaf, layer = grouped.program_key(
        name, lambda l: ref.group_of(m, l))
    return f"{group}.{leaf}", layer


def _leaf(tree, m, name):
    key, layer = _program_key(m, name)
    group, leaf = key.split(".", 1)
    return tree[group][leaf] if layer is None else tree[group][leaf][layer]


def _ref_loss(tree, ids, m):
    """``(loss, (counts [layers, held], state RMS [rows, layers]))``."""
    rows = [ref.row_loss_sum(tree, jnp.asarray(ids[r]), jnp.asarray(ids[r]),
                             m) for r in range(ids.shape[0])]
    return (sum(r[0] for r in rows)
            / (ids.shape[0] * (ids.shape[1] - 1)),
            (sum(r[1][0] for r in rows), jnp.stack([r[1][1] for r in rows])))


def _ref_loss_and_grads(tree, ids, m):
    """Jitted: ``((loss, (counts, rms)), grads)``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda t: _ref_loss(t, ids, m), has_aux=True))(tree)


def _parameters(model):
    return {n: a._data for n, a in model.state_dict().items()
            if not n.endswith("e_score_correction_bias")}


def _program_loss_and_grads(model, ids):
    ids = jnp.asarray(ids)
    frozen = {n: a._data for n, a in model.state_dict().items()
              if n.endswith("e_score_correction_bias")}

    def loss(p):
        logits = functional_call(model, {**p, **frozen}, ids)
        counters = {k: v for k, v in model.step_counters().items()}
        return model.loss(Tensor(logits), ids)._data, counters

    (value, counters), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(_parameters(model))
    return value, grads, counters


# ----------------------------------------- program against the reference --
def test_loss_gradients_and_counters_match_the_reference(share="uncut"):
    """(The chip's share runs through ``jit.TrainStep`` in the next test.)"""
    m, model, tree = _seeded(share)
    ids = _batch(1)
    with jax.default_matmul_precision("highest"):
        got_loss, got, counters = _program_loss_and_grads(model, ids)
    (want_loss, (want_counts, want_rms)), want = _ref_loss_and_grads(
        tree, ids, m)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name in _parameters(model):
        w = np.asarray(_leaf(want, m, name))
        # zeros enter the first layer's router: its gamma has no gradient
        assert (np.abs(w).max() > 0) \
            == (name != "model.layers.0.moe.router.state_gain"), name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)
    counts = np.asarray(counters["moe_tokens_per_expert"])
    assert counts.shape == (3, m["num_experts"])
    np.testing.assert_array_equal(counts, np.asarray(want_counts))
    if share == "uncut":
        assert counts.sum() == 3 * 2 * 48           # top-1, nothing dropped
    rms = np.asarray(counters["router_state_rms"])
    assert rms[0] == 0.0
    np.testing.assert_allclose(
        rms, np.sqrt(np.mean(np.asarray(want_rms) ** 2, axis=0)), rtol=1e-4)
    # the head is the embedding, its gradient the sum of its two uses: a
    # row no input drew still has the head's
    unseen = np.setdiff1d(np.arange(BASE["vocab_size"]), ids.ravel())
    embed = np.asarray(got["model.embeddings.weight"])
    assert len(unseen) and np.abs(embed[unseen]).max() > 0


def test_two_steps_of_adamw_follow_the_reference():
    """Float32 all through (no amp), the step object the cell times: losses,
    counters, and where every leaf stands after two steps."""
    m, model, _ = _seeded()
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=REMAT)
    batches = [(_batch(s), _batch(s)) for s in (3, 4)]
    with jax.default_matmul_precision("highest"):
        losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
                  for ids, _ in batches]
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


def test_the_router_state_crosses_rematerialised_blocks_unchanged():
    """Three layers: the state a block's router hands on is an OUTPUT of the
    block, so ``TrainStep(remat=...)`` changes no loss, no counter and no
    parameter."""
    ids = paddle.to_tensor(_batch(5))
    seen = []
    for remat in (False, REMAT):
        _, model, _ = _seeded("uncut")
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=remat)
        loss = float(step(ids, ids))
        seen.append((loss, np.asarray(step.counters["router_state_rms"]),
                     {n: np.asarray(p) for n, p in
                      step.state_dict()["params"].items()}))
    for loss, rms, params in seen[1:]:
        assert loss == pytest.approx(seen[0][0], rel=1e-6)
        np.testing.assert_allclose(rms, seen[0][1], rtol=1e-6)
        assert rms[0] == 0.0 and (rms[1:] > 0).all()
        for name, p in seen[0][2].items():
            np.testing.assert_allclose(p, params[name], rtol=1e-4,
                                       atol=1e-6, err_msg=name)


def test_the_stated_bfloat16_mix_stays_near_the_reference():
    """``amp.decorate`` O2: parameters, matmul operands, the residual stream
    and the gradients in bfloat16; the temperature, the residual scales and
    the router from its state on STAY float32 (``amp_keep_float32``), and
    the reference stores them so (``FLOAT32_LEAVES``).  The band: the loss
    within 0.5% of the float32 reference's ON THE SAME weights, every
    leaf's gradient within 10% of its norm and pointing its way."""
    m, model, tree = _seeded(dtype=jnp.bfloat16)
    kept = {n for n, t in _parameters(model).items()
            if t.dtype == jnp.float32}
    assert {n.split(".", 3)[3] for n in kept} == set(ref.FLOAT32_LEAVES)
    assert all(tree["blocks"][leaf].dtype == jnp.float32
               for leaf in ref.FLOAT32_LEAVES)
    ids = _batch(8)
    loss, grads, _ = _program_loss_and_grads(model, ids)
    tree32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    (want, _), want_grads = _ref_loss_and_grads(tree32, ids, m)
    assert loss.dtype == jnp.float32
    assert float(loss) == pytest.approx(float(want), rel=5e-3)
    for name, g in grads.items():
        assert g.dtype == (jnp.float32 if name in kept else jnp.bfloat16)
        g = np.asarray(g.astype(jnp.float32)).ravel()
        w = np.asarray(_leaf(want_grads, m, name)).ravel()
        if not w.any():         # the first layer's gamma: zeros enter it
            assert not g.any(), name
            continue
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.1, name
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.98, name


