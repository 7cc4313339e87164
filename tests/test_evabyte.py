"""A byte-level decoder whose attention is exact inside a block-aligned
window and reads chunk summaries of every window before it, with several
next-byte heads (``models/evabyte.py`` on the shell of
``models/moe_decoder.py``), at a size the CPU runs, on seeded weights:

- the model against the plain reference ``chipbench/reference/evabyte.py``:
  logits, loss, every leaf's gradient (``mu`` and ``phi`` by name), two steps
  of AdamW through ``jit.TrainStep``; in float32, and in the stated
  bfloat16 mix;
- the reference's attention, a head and a block of rows at a time, against
  ONE dense ``[T, T + T / C]`` score matrix under an explicit mask;
- what can go wrong, planted, must FAIL the comparison: the own window's
  summaries counted too, a sliding window in the block one's place,
  ``|k|^2 / 2`` left out, head ``p`` shifted by ``p`` and not ``p + 1``, a
  bfloat16 residual;
- the shell's three options are off for the two families that were there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models import evabyte, laguna, mla_moe, moe_decoder
from paddle_tpu.models.evabyte import EvaByteForCausalLM

from chipbench.reference import evabyte as ref
from chipbench.runners import evabyte_train as runner

# hidden 64, 4 heads of 16, windows of 32 in chunks of 4, four windows a
# row, 2 layers, 3 next-byte heads
BASE = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=128, window_size=32,
            chunk_size=4, num_pred_heads=3, rope_theta=100000,
            rms_norm_eps=1e-5, init_std=0.05, vocab_size=320)
SEQ = 128
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}


def _seeded(seed=7, dtype=jnp.float32, **over):
    """(model group, program model holding the reference's seeded weights,
    the reference's tree).  The gains' offsets and the pooling vectors are
    pushed off their starting values, so that a dropped offset or vector
    shows."""
    m = runner.model_group({**BASE, **over})
    paddle.seed(0)
    model = EvaByteForCausalLM(runner.model_config(m))
    tree = ref.init_params(seed, m, dtype)
    key = jax.random.PRNGKey(seed)
    for group, leaf in (("blocks", "ln_1.weight"), ("blocks", "ln_2.weight"),
                        ("head", "ln_f.weight")):
        key, sub = jax.random.split(key)
        a = tree[group][leaf]
        tree[group][leaf] = (0.1 * jax.random.normal(sub, a.shape)) \
            .astype(a.dtype)
    for leaf in ("attn.mu", "attn.phi"):
        tree["blocks"][leaf] = tree["blocks"][leaf] * 4
    if dtype != jnp.float32:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    runner.load_seeded(model, tree)
    return m, model, tree


def _ids(seed=0, rows=2, seq=SEQ):
    return np.random.RandomState(seed).randint(
        0, BASE["vocab_size"], (rows, seq)).astype("int32")


def _ref_loss(tree, ids, m):
    return sum(ref.row_loss(tree, jnp.asarray(r), jnp.asarray(r), m)
               for r in ids) / ids.shape[0]


def _program_loss_and_grads(model, ids):
    names = list(model.state_dict())
    params = {n: model.state_dict()[n]._data for n in names}

    def loss(p):
        logits = functional_call(model, p, jnp.asarray(ids))
        return model.loss(Tensor(logits), Tensor(jnp.asarray(ids)))._data

    return jax.value_and_grad(loss)(params)


def _leaf(tree, name):
    group, leaf, layer = runner.program_key(name)
    a = tree[group][leaf]
    return a if layer is None else a[layer]


@pytest.fixture(scope="module")
def float32_run():
    """Program and reference on one batch, once: logits, loss, gradients."""
    m, model, tree = _seeded()
    ids = _ids(1)
    with jax.default_matmul_precision("highest"):
        logits = model(paddle.to_tensor(ids))._data
        want_logits = [ref.forward_row(tree, jnp.asarray(row), m)
                       for row in ids]
        loss, grads = _program_loss_and_grads(model, ids)
        want_loss, want_grads = jax.value_and_grad(_ref_loss)(tree, ids, m)
    return dict(names=list(model.state_dict()), logits=logits,
                want_logits=want_logits, loss=loss, grads=grads,
                want_loss=want_loss, want_grads=want_grads)


def test_logits_match_the_reference(float32_run):
    got = float32_run["logits"]
    assert got.shape == (2, SEQ, 3, 320) and got.dtype == jnp.float32
    for r, logits in enumerate(float32_run["want_logits"]):
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(logits),
                                   rtol=2e-5, atol=2e-5)


def test_loss_matches_the_reference(float32_run):
    assert float(float32_run["loss"]) == pytest.approx(
        float(float32_run["want_loss"]), rel=2e-5)


def test_every_leafs_gradient_matches_the_reference(float32_run):
    names, got, want = (float32_run[k] for k in ("names", "grads",
                                                 "want_grads"))
    assert {"model.layers.0.attn.mu", "model.layers.1.attn.phi"} <= set(names)
    for name in names:
        w = np.asarray(_leaf(want, name))
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)


def test_the_stated_bfloat16_mix_stays_near_the_reference():
    """``amp.decorate`` O2: parameters and matmul operands in bfloat16, the
    residual stream, the norms' statistics, the scores and the softmaxes in
    float32.  A bfloat16 operand carries 8 bits (relative step 2 ** -8 =
    0.4%); through two layers of 64-wide sums the logits, of order 0.3
    here, come out within 0.01 and the loss, a mean over 762 targets,
    within 2e-3; a leaf's gradient keeps its norm within 3% and its
    direction within a cosine of 0.995 (the pooling vectors', sums of few
    small terms, within 0.98)."""
    m, model, tree = _seeded(dtype=jnp.bfloat16)
    ids = _ids(2)
    with jax.default_matmul_precision("highest"):
        logits = model(paddle.to_tensor(ids))._data
        want_logits = ref.forward_row(tree, jnp.asarray(ids[0]), m)
        got_loss, got = _program_loss_and_grads(model, ids)
        want_loss, want = jax.value_and_grad(_ref_loss)(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree),
            ids, m)
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(want_logits), atol=0.01)
    assert abs(float(got_loss) - float(want_loss)) < 2e-3
    for name in model.state_dict():
        g = np.asarray(got[name], np.float32).ravel()
        w = np.asarray(_leaf(want, name), np.float32).ravel()
        pool = name.endswith(("attn.mu", "attn.phi"))
        cos = float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w)))
        assert cos > (0.98 if pool else 0.995), (name, cos)
        assert np.linalg.norm(g) == pytest.approx(
            np.linalg.norm(w), rel=0.06 if pool else 0.03), name


def test_two_steps_of_adamw_follow_the_reference():
    """Float32 all through (no amp), the step object the cell times:
    losses, and where every leaf stands after two steps."""
    m, model, _ = _seeded()
    # the reference starts from ITS seeded tree: load that, untouched
    tree = ref.init_params(7, m, jnp.float32)
    runner.load_seeded(model, tree)
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=["eva_attention_out", "eva_attention_lse"])
    batches = [(_ids(s), _ids(s)) for s in (3, 4)]
    with jax.default_matmul_precision("highest"):
        losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for x, y in batches]
        want = ref.train_reference(7, m, batches, HP, jnp.float32)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    counters = {k: np.asarray(v) for k, v in step.counters.items()}
    # off the chip the dense composition runs: 128 x (128 + 32) pairs
    assert counters["eva_pairs_scored"].tolist() == [128 * 160] * 2
    assert counters["eva_pairs_needed"].tolist() == [
        4 * 32 * 33 // 2 + 32 * 8 * 6] * 2
    sd = step.state_dict()["params"]
    got = ref.change_norms(7, m, jnp.float32, {
        (".".join(runner.program_key(n)[:2]), runner.program_key(n)[2]): a
        for n, a in sd.items()})
    for key, w in want["param_change_norms"].items():
        assert got[key] == pytest.approx(w, rel=2e-3, abs=1e-7), key


def test_the_references_attention_is_one_dense_masked_softmax():
    """``reference.attend`` runs a head and 32 rows at a time; here the
    same function of q, k, v, kt, vt from ONE ``[T, T + T / C]`` score
    matrix a head, the mask written out pair by pair."""
    m = runner.model_group(BASE)
    z = ref.sizes(m)
    t, n, d, w, c = SEQ, z["n"], z["d"], z["window"], z["chunk"]
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k, v = (jax.random.normal(kk, (t, n, d)) for kk in keys[:3])
    kt, vt = (jax.random.normal(kk, (t // c, n, d)) for kk in keys[3:])
    mask = np.zeros((t, t + t // c), bool)
    for i in range(t):
        for j in range(t):
            mask[i, j] = j // w == i // w and j <= i
        for g in range(t // c):
            mask[i, t + g] = (g * c) // w < i // w
    assert mask[w, :t].sum() == 1 and mask[w, t:].sum() == w // c
    assert mask[w - 1, :t].sum() == w and not mask[w - 1, t:].any()
    np.testing.assert_array_equal(np.asarray(ref.seen(jnp.arange(t), t, m)),
                                  mask)
    with jax.default_matmul_precision("highest"):
        got = ref.attend(q, k, v, kt, vt, m, rows=32)
        s = jnp.einsum("tnd,snd->nts", q, jnp.concatenate([k, kt])) \
            * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        want = jnp.einsum("nts,snd->tnd", p, jnp.concatenate([v, vt]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ---- planted faults: each must fail the comparison the sound model passes

def _plant(monkeypatch, fault):
    if fault in ("own-summaries", "sliding-window"):
        def wrong(q, k, v, kt, vt, window, chunk):
            t = q.shape[1]
            pos = jnp.arange(t)
            back = pos[:, None] - pos[None, :]
            own = (pos[:, None] // window == pos[None, :] // window) \
                & (back >= 0)
            before = (jnp.arange(t // chunk)[None, :] * chunk) // window
            seen = before < pos[:, None] // window
            if fault == "own-summaries":
                seen = before <= pos[:, None] // window
            else:
                own = (back >= 0) & (back < window)
            s = q.shape[-1] ** -0.5
            sc = jnp.concatenate(
                [jnp.where(own, jnp.einsum("btnh,bsnh->bnts", q, k) * s,
                           -jnp.inf),
                 jnp.where(seen, jnp.einsum("btnh,bcnh->bntc", q, kt) * s,
                           -jnp.inf)], -1)
            p = jax.nn.softmax(sc, -1)
            return jnp.einsum("bnts,bsnh->btnh", p[..., :t], v) \
                + jnp.einsum("bntc,bcnh->btnh", p[..., t:], vt)
        # the model's own call, not the dispatcher under it: the eager op
        # cache would hand a later test the faulty trace
        monkeypatch.setattr(
            evabyte, "_eva_agg", lambda *a: Tensor(wrong(
                *(x._data for x in a[:5]), *a[5:])))
    elif fault == "no-half-square-norm":
        def prep(k, v, mu, phi, chunk):
            k, v, mu, phi = k._data, v._data, mu._data, phi._data
            b, t, n, d = k.shape
            s = d ** -0.5
            kc = k.reshape(b, t // chunk, chunk, n, d)
            vc = v.reshape(b, t // chunk, chunk, n, d)
            alpha = jax.nn.softmax(s * jnp.sum(kc * mu, -1), axis=2)
            gamma = jax.nn.softmax(s * jnp.sum(kc * phi, -1), axis=2)
            return (Tensor(jnp.sum(alpha[..., None] * kc, 2)),
                    Tensor(jnp.sum(gamma[..., None] * vc, 2)))
        monkeypatch.setattr(evabyte, "_eva_prep", prep)
    elif fault == "heads-shifted-by-p":
        def loss(self, logits, labels):
            b, t, heads, vocab = logits.shape
            total = 0.0
            for p in range(heads):      # head p held to byte t + p
                lp = jax.nn.log_softmax(logits._data[:, :t - p, p], -1)
                picked = jnp.take_along_axis(
                    lp, labels._data[:, p:, None], -1)
                total = total - jnp.mean(picked)
            return Tensor(total / heads)
        monkeypatch.setattr(moe_decoder.MoeDecoderForCausalLM,
                            "multi_head_loss", loss)
    elif fault == "bfloat16-residual":
        monkeypatch.setattr(evabyte.EvaByteConfig, "fp32_skip_add", False)
    else:
        raise ValueError(fault)


FLOAT32_FAULTS = ["own-summaries", "sliding-window", "no-half-square-norm",
                  "heads-shifted-by-p"]


@pytest.mark.parametrize("fault", FLOAT32_FAULTS)
def test_a_planted_fault_fails_the_float32_comparison(monkeypatch, fault):
    """The comparisons of ``test_logits_...`` and ``test_loss_...`` on the
    program with one thing wrong: the loss or the logits leave the
    tolerance the sound program keeps (2e-5), by a wide margin."""
    _plant(monkeypatch, fault)
    m, model, tree = _seeded()
    ids = _ids(1, rows=1)
    with jax.default_matmul_precision("highest"):
        got = model(paddle.to_tensor(ids))
        got_loss = float(model.loss(got, paddle.to_tensor(ids)))
        want = ref.forward_row(tree, jnp.asarray(ids[0]), m)
        want_loss = float(_ref_loss(tree, ids, m))
    logit_gap = float(jnp.max(jnp.abs(got._data[0] - want)))
    loss_gap = abs(got_loss - want_loss) / want_loss
    if fault == "heads-shifted-by-p":
        assert logit_gap < 2e-5         # the forward is sound
        assert loss_gap > 1e-3, loss_gap
    else:
        assert logit_gap > 1e-3, logit_gap


def test_a_bfloat16_residual_fails_the_bfloat16_comparison(monkeypatch):
    """With the residual stream in bfloat16 every add rounds x to 8 bits:
    the logits leave the 0.01 the stated mix keeps."""
    m, model, tree = _seeded(dtype=jnp.bfloat16)
    ids = _ids(2)
    want = ref.forward_row(tree, jnp.asarray(ids[0]), m)

    def gap(model):
        with jax.default_matmul_precision("highest"):
            got = model(paddle.to_tensor(ids))._data[0]
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))

    sound = gap(model)
    _plant(monkeypatch, "bfloat16-residual")
    _, broken, _ = _seeded(dtype=jnp.bfloat16)
    assert sound < 0.01 < gap(broken), (sound, gap(broken))


# ---- the shell

def test_the_shells_options_are_off_for_the_families_that_were_there():
    for config in (laguna.LagunaConfig(), mla_moe.MlaMoeConfig()):
        assert (config.fp32_skip_add, config.norm_add_unit_offset,
                config.num_pred_heads) == (False, False, 1)
        assert type(config.make_norm()) is nn.RMSNorm
    config = evabyte.EvaByteConfig()
    assert (config.fp32_skip_add, config.norm_add_unit_offset,
            config.num_pred_heads) == (True, True, 3)
    assert type(config.make_norm()) is moe_decoder.UnitOffsetRMSNorm


def test_the_residual_stream_is_float32_under_amp():
    """``fp32_skip_add``: every layer is handed, and hands on, float32,
    whatever the parameters' dtype; the attention and the MLP are handed
    the parameters' dtype."""
    _, model, _ = _seeded(dtype=jnp.bfloat16, num_hidden_layers=1)
    seen = []
    for layer in model.model.layers:
        layer.register_forward_post_hook(
            lambda l, inp, out: seen.append((inp[0].dtype, out[0].dtype)))
        layer.attn.register_forward_post_hook(
            lambda l, inp, out: seen.append((inp[0].dtype, out.dtype)))
    model(paddle.to_tensor(_ids(0, rows=1)))
    kinds = {str(a) + ">" + str(b) for a, b in seen}
    assert kinds == {"float32>float32", "bfloat16>bfloat16"}, kinds


def test_rows_must_be_whole_windows():
    model = evabyte.evabyte_tiny()
    with pytest.raises(ValueError, match="whole windows"):
        model(paddle.to_tensor(_ids(0, rows=1, seq=48)))


def test_the_published_sizes():
    """The cell's model counted from its sizes: 4 of 32 layers."""
    h, inter, n, d, p, v = 4096, 11008, 32, 128, 8, 320
    layer = 4 * h * h + 3 * h * inter + 2 * h + 2 * n * d
    assert layer == 202_391_552
    assert 4 * layer + v * h + h * p * v + h == 821_366_784
    assert 32 * layer + v * h + h * p * v + h == 6_488_330_240
