"""An expert decoder whose router reads a block's input BEFORE attention and
whose experts are ReGLU (``models/smallthinker.py`` on the shell of
``models/moe_decoder.py``), at a size the CPU runs, on seeded weights,
against the plain reference ``chipbench/reference/smallthinker.py`` (float32,
``highest``, independent of ``paddle_tpu``): counters, loss, every leaf's
gradient whole and as a share, two steps of AdamW through ``jit.TrainStep``
with its layers rematerialised, and the stated bfloat16 mix.  The pieces one
by one, the share test and the kernels are in
``tests/test_smallthinker_pieces.py`` (a file of its own so that the suite's
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
from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM

from chipbench.reference import smallthinker as ref
from chipbench.runners import laguna_train as grouped
from chipbench.runners import smallthinker_train as runner

# one period (full without a position encoding, then three window layers
# with the rotation), 6 q heads over 2 kv heads of 16 (a group of 3), a
# window of 8 in rows of 48, a router 8 wide with 3 a token
BASE = dict(hidden_size=64, num_hidden_layers=4, num_attention_heads=6,
            num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
            moe_num_active_primary_experts=3,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
            sliding_window_size=8, rope_theta=1500000, rope_scaling=None,
            max_position_embeddings=16384, rms_norm_eps=1e-6,
            tie_word_embeddings=False, vocab_size=96, embedding_range=1.0)
SHARES = {"uncut": dict(moe_num_primary_experts=8, deployment={}),
          "share-2-of-8-from-4": dict(
              moe_num_primary_experts=2,
              deployment={"router_experts": 8, "expert_offset": 4})}
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}
REMAT = ["flash_attention_out", "flash_attention_lse"]


def seeded(share="share-2-of-8-from-4", seed=7, dtype=jnp.float32, **more):
    """(model group, program model holding the reference's seeded weights,
    the reference's tree)."""
    m = runner.model_group({**BASE, **SHARES[share], **more})
    paddle.seed(0)
    model = SmallThinkerForCausalLM(runner.model_config(m))
    if dtype != jnp.float32:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    tree = ref.init_params(seed, m, dtype)
    grouped.load_seeded(model, tree, ref, m)
    return m, model, tree


def batch(step=0, rows=2, seq=48):
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
    """``(loss, (counts [layers, held], unserved [layers]))``."""
    rows = [ref.row_loss_sum(tree, jnp.asarray(ids[r]), jnp.asarray(ids[r]),
                             m) for r in range(ids.shape[0])]
    return (sum(r[0] for r in rows) / (ids.shape[0] * (ids.shape[1] - 1)),
            (sum(r[1][0] for r in rows), sum(r[1][1] for r in rows)))


def ref_loss_and_grads(tree, ids, m):
    """Jitted: ``((loss, (counts, unserved)), grads)``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda t: _ref_loss(t, ids, m), has_aux=True))(tree)


def _parameters(model):
    return {n: a._data for n, a in model.state_dict().items()}


def program_loss_and_grads(model, ids):
    ids = jnp.asarray(ids)

    def loss(p):
        logits = functional_call(model, p, ids)
        return model.loss(Tensor(logits), ids)._data, \
            dict(model.step_counters())

    (value, counters), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(_parameters(model))
    return value, grads, counters


# ----------------------------------------- program against the reference --
@pytest.mark.parametrize("share", list(SHARES))
def test_loss_gradients_and_counters_match_the_reference(share):
    m, model, tree = seeded(share)
    ids = batch(1)
    with jax.default_matmul_precision("highest"):
        got_loss, got, counters = program_loss_and_grads(model, ids)
    (want_loss, (want_counts, want_unserved)), want = ref_loss_and_grads(
        tree, ids, m)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name in _parameters(model):
        w = np.asarray(_leaf(want, m, name))
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)
    counts = np.asarray(counters["moe_tokens_per_expert"])
    assert counts.shape == (4, m["moe_num_primary_experts"])
    np.testing.assert_array_equal(counts, np.asarray(want_counts))
    unserved = float(counters["moe_tokens_unserved"])
    assert unserved == pytest.approx(float(np.mean(want_unserved)))
    if share == "uncut":
        assert counts.sum() == 4 * 3 * 2 * 48       # top-3, nothing dropped
        assert unserved == 0.0
    else:
        assert 0 < unserved < 2 * 48


def test_two_steps_of_adamw_follow_the_reference():
    """Float32 all through (no amp), the step object the cell times, every
    layer rematerialised with its own input handed to its router: losses,
    counters, and where every leaf stands after two steps."""
    m, model, _ = seeded()
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=REMAT)
    batches = [(batch(s), batch(s)) for s in (3, 4)]
    with jax.default_matmul_precision("highest"):
        losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
                  for ids, _ in batches]
        counts = np.asarray(step.counters["moe_tokens_per_expert"])
        unserved = float(step.counters["moe_tokens_unserved"])
        params = {_program_key(m, n): np.asarray(a)
                  for n, a in step.state_dict()["params"].items()}
        want = ref.train_reference(7, m, batches, HP, jnp.float32,
                                   against=params)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    np.testing.assert_array_equal(counts, want["expert_counts"][1])
    assert unserved == pytest.approx(np.mean(want["tokens_unserved"][1]))
    assert min(want["param_change_cosines"].values()) > 0.999
    for key, a in want["params"].items():
        np.testing.assert_allclose(params[key], np.asarray(a), rtol=1e-3,
                                   atol=2e-5, err_msg=str(key))
    norms = ref.change_norms(7, m, jnp.float32, params)
    for key, w in want["param_change_norms"].items():
        assert norms[key] == pytest.approx(w, rel=2e-3), key


def test_rematerialised_blocks_change_nothing():
    """The block's input reaches its router inside ``fleet.recompute`` too:
    ``TrainStep(remat=...)`` changes no loss, no counter, no parameter."""
    ids = paddle.to_tensor(batch(5))
    seen = []
    for remat in (False, REMAT, True):
        _, model, _ = seeded()
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=remat)
        loss = float(step(ids, ids))
        seen.append((loss, {k: np.asarray(v)
                            for k, v in step.counters.items()},
                     {n: np.asarray(p) for n, p in
                      step.state_dict()["params"].items()}))
    for loss, counters, params in seen[1:]:
        assert loss == pytest.approx(seen[0][0], rel=1e-6)
        for name, c in seen[0][1].items():
            np.testing.assert_array_equal(c, counters[name])
        for name, p in seen[0][2].items():
            np.testing.assert_allclose(p, params[name], rtol=1e-4,
                                       atol=1e-6, err_msg=name)


def test_the_stated_bfloat16_mix_stays_near_the_reference():
    """``amp.decorate`` O2: parameters, matmul operands, the residual stream
    and the gradients in bfloat16, the router's scores in float32.  The
    band: the loss within 0.5% of the float32 reference's ON THE SAME
    weights, every leaf's gradient within 10% of its norm and pointing its
    way."""
    m, model, tree = seeded(dtype=jnp.bfloat16)
    ids = batch(8)
    loss, grads, _ = program_loss_and_grads(model, ids)
    tree32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    (want, _), want_grads = ref_loss_and_grads(tree32, ids, m)
    assert loss.dtype == jnp.float32
    assert float(loss) == pytest.approx(float(want), rel=5e-3)
    for name, g in grads.items():
        assert g.dtype == jnp.bfloat16, name
        g = np.asarray(g.astype(jnp.float32)).ravel()
        w = np.asarray(_leaf(want_grads, m, name)).ravel()
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.1, name
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.98, name


def test_the_published_preset_states_the_source_s_widths(monkeypatch):
    """No model is built: the config of ``smallthinker_21b_a3b`` alone."""
    from paddle_tpu.models import smallthinker as st

    monkeypatch.setattr(st, "SmallThinkerForCausalLM", lambda config: config)
    c = st.smallthinker_21b_a3b(num_hidden_layers=4, num_local_experts=16,
                                vocab_size=37984)
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim) == (2560, 28, 4, 128)
    assert (c.moe_ffn_hidden_size, c.moe_num_primary_experts,
            c.moe_num_active_primary_experts) == (768, 64, 6)
    assert c.rope_layout == c.sliding_window_layout == (0, 1, 1, 1)
    assert (c.sliding_window_size, c.rope_theta) == (4096, 1500000)
    assert (c.num_local_experts, c.expert_offset, c.vocab_size) \
        == (16, 0, 37984)
    assert c.router_reads_block_input
    with pytest.raises(NotImplementedError):
        st.SmallThinkerConfig(moe_primary_router_apply_softmax=False)
    with pytest.raises(ValueError):
        st.SmallThinkerConfig(rope_layout=(0, 1))
