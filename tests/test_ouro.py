"""A decoder whose whole layer stack runs ``total_ut_steps`` times over the
same parameters, an exit gate weighing each pass's loss (``models/ouro.py``
on the shell of ``models/moe_decoder.py``), at a size the CPU runs, on
seeded weights:

- the model against the plain reference ``chipbench/reference/ouro.py``:
  loss, every pass's cross entropy, every pass's exit probability, EVERY
  leaf's gradient, two steps of AdamW through ``jit.TrainStep``; in
  float32, and in the stated bfloat16 mix;
- the shared-weight gradient is the sum, over the ``R x L`` unshared copies
  of a model built with tied values, of each copy's gradient;
- ``total_ut_steps`` 1 with the post-branch norm off is the shell as it
  was; rematerialisation changes nothing; the exit distribution sums to 1
  and a gate forced shut under ``beta`` 0 gives the last pass's plain cross
  entropy;
- what can go wrong, planted, must FAIL the comparison: a pass too few, the
  state fed back before ``ln_f``, the earlier passes' gradient cut, ``beta``
  0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models import (OuroConfig, OuroForCausalLM, moe_decoder,
                               ouro_2_6b, ouro_tiny)
from paddle_tpu.nn import functional as F

from chipbench.reference import ouro as ref
from chipbench.runners import ouro_train as runner

# hidden 64, 4 heads of 16 over 2 kv heads, 3 layers, 4 passes
BASE = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=96,
            total_ut_steps=4, early_exit_threshold=1.0, rope_theta=1000000,
            rms_norm_eps=1e-6, vocab_size=512, exit_entropy_beta=0.05,
            initializer_range=0.05)
SEQ = 48
HP = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
      "weight_decay": 0.1}
REMAT = ["flash_attention_out", "flash_attention_lse"]


def _seeded(seed=7, dtype=jnp.float32, **over):
    """(model group, program model holding the reference's seeded weights,
    the reference's tree)."""
    m = runner.model_group({**BASE, **over})
    paddle.seed(0)
    model = OuroForCausalLM(runner.model_config(m))
    tree = ref.init_params(seed, m, dtype)
    if dtype != jnp.float32:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    runner.load_seeded(model, tree)
    return m, model, tree


def _ids(seed=0, rows=2, seq=SEQ):
    return np.random.RandomState(seed).randint(
        0, BASE["vocab_size"], (rows, seq)).astype("int32")


def _ref_loss(tree, ids, m, **kw):
    """The batch's loss, and the rows' mean ``(pass_loss, exit_mass)``."""
    rows = [ref.row_loss(tree, jnp.asarray(r), jnp.asarray(r), m, **kw)
            for r in ids]
    n = len(rows)
    return (sum(r[0] for r in rows) / n,
            tuple(sum(r[1][i] for r in rows) / n for i in range(2)))


def _params(model):
    return {n: t._data for n, t in model.state_dict().items()}


def _program_loss(model, p, ids):
    out = functional_call(model, p, jnp.asarray(ids), jnp.asarray(ids))
    return model.loss(tuple(Tensor(o) for o in out),
                      Tensor(jnp.asarray(ids)))._data


def _program_loss_and_grads(model, ids):
    return jax.value_and_grad(lambda p: _program_loss(model, p, ids))(
        _params(model))


def _leaf(tree, name):
    group, leaf, layer = runner.program_key(name)
    a = tree[group][leaf]
    return a if layer is None else a[layer]


@pytest.fixture(scope="module")
def float32_run():
    """Program and reference on one batch, once."""
    m, model, tree = _seeded()
    ids = _ids(1)
    with jax.default_matmul_precision("highest"):
        each, p, entropy, weight = (
            t._data for t in model(paddle.to_tensor(ids)))
        counters = model.step_counters()
        want_rows = [ref.passes_of(tree, jnp.asarray(r), jnp.asarray(r), m)
                     for r in ids]
        loss, grads = _program_loss_and_grads(model, ids)
        (want_loss, want_aux), want_grads = jax.value_and_grad(
            _ref_loss, has_aux=True)(tree, ids, m)
    return dict(names=list(model.state_dict()), each=each, p=p,
                entropy=entropy, weight=weight, counters=counters,
                want_rows=want_rows, loss=loss, grads=grads,
                want_loss=want_loss, want_aux=want_aux,
                want_grads=want_grads)


def test_loss_matches_the_reference(float32_run):
    assert float(float32_run["loss"]) == pytest.approx(
        float(float32_run["want_loss"]), rel=2e-5)


@pytest.mark.parametrize("r", range(4))
def test_every_pass_loss_matches_the_reference(float32_run, r):
    got = float32_run["each"]
    assert got.shape == (4, 2, SEQ) and got.dtype == jnp.float32
    assert float(jnp.abs(got[:, :, -1]).max()) == 0.0    # no target there
    for row, (want, _) in enumerate(float32_run["want_rows"]):
        np.testing.assert_allclose(np.asarray(got[r, row, :-1]),
                                   np.asarray(want[r]), rtol=2e-5, atol=2e-5)
    assert float(float32_run["counters"]["ouro_pass_loss"][r]) == \
        pytest.approx(float(float32_run["want_aux"][0][r]), rel=2e-5)


@pytest.mark.parametrize("r", range(4))
def test_every_exit_probability_matches_the_reference(float32_run, r):
    got = float32_run["p"]
    for row, (_, lams) in enumerate(float32_run["want_rows"]):
        want, _ = ref.exit_distribution(list(lams))
        np.testing.assert_allclose(np.asarray(got[r, row, :-1]),
                                   np.asarray(want[r]), rtol=2e-5, atol=1e-6)
    assert float(float32_run["counters"]["ouro_exit_mass"][r]) == \
        pytest.approx(float(float32_run["want_aux"][1][r]), rel=2e-5)


def test_every_leafs_gradient_matches_the_reference(float32_run):
    names, got, want = (float32_run[k] for k in ("names", "grads",
                                                 "want_grads"))
    assert {"exit_gate.weight", "exit_gate.bias", "model.ln_f.weight",
            "model.layers.0.ln_1b.weight", "model.layers.2.ln_2b.weight",
            "lm_head.weight", "model.embeddings.weight"} <= set(names)
    assert len(names) == 3 * 10 + 5
    for name in names:
        w = np.asarray(_leaf(want, name))
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=name)


def test_exit_distribution_sums_to_one(float32_run):
    p, entropy = float32_run["p"], float32_run["entropy"]
    np.testing.assert_allclose(np.asarray(p.sum(axis=0)), 1.0, atol=1e-6)
    assert float(p.min()) > 0 and float(entropy.min()) > 0
    assert float(entropy.max()) <= np.log(4) + 1e-6
    assert float(float32_run["counters"]["ouro_exit_mass"].sum()) == \
        pytest.approx(1.0, abs=1e-6)
    # 1 / (positions with a target) there, 0 at a row's last position
    w = np.asarray(float32_run["weight"])
    assert w[:, -1].max() == 0 and w[:, :-1].min() == w.max()
    assert w.sum() == pytest.approx(1.0, rel=1e-6)


def test_a_shut_gate_and_no_entropy_term_give_the_last_pass_loss():
    m, model, tree = _seeded(exit_entropy_beta=0.0)
    shut = jnp.full((1,), -1e4, jnp.float32)
    model.exit_gate.bias._data = shut
    tree["head"]["exit_gate.bias"] = shut
    ids = _ids(3)
    with jax.default_matmul_precision("highest"):
        loss, grads = _program_loss_and_grads(model, ids)
        each, p, _, _ = (t._data for t in model(paddle.to_tensor(ids)))
        want, _ = _ref_loss(tree, ids, m)
    assert float(p[-1].min()) == 1.0 and float(p[:-1].max()) == 0.0
    assert float(loss) == pytest.approx(float(each[-1, :, :-1].mean()),
                                        rel=1e-6)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads.values())
    assert float(jnp.abs(grads["exit_gate.weight"]).max()) == 0.0


def test_a_position_without_a_target_is_left_out():
    """``-100`` at ``labels[t + 1]`` takes position ``t`` out of the loss,
    the counters and the mean."""
    _, model, _ = _seeded()
    ids = _ids(4)
    labels = ids.copy()
    labels[:, SEQ // 2:] = -100
    with jax.default_matmul_precision("highest"):
        each, p, entropy, weight = (t._data for t in model(
            paddle.to_tensor(ids), paddle.to_tensor(labels)))
        whole = model(paddle.to_tensor(ids))[0]._data
    kept = SEQ // 2 - 1
    assert float(jnp.abs(each[:, :, kept:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(each[:, :, :kept]),
                               np.asarray(whole[:, :, :kept]), rtol=1e-6)
    assert float(weight[:, :kept].min()) == pytest.approx(1 / (2 * kept))
    assert float(weight[:, kept:].max()) == 0.0


# --------------------------------------------- the shared-weight gradient --
def _unshared_loss(copies, outer, ids, m):
    """The reference's loss with ``R x L`` block parameter sets of their
    own: pass ``r`` runs ``copies[r]``."""
    eps, total = float(m["rms_norm_eps"]), 0.0
    for row in ids:
        row = jnp.asarray(row)
        x = outer["embed"]["weight"][row]
        each, lams = [], []
        for blocks in copies:
            for l in range(m["num_hidden_layers"]):
                x = ref.block(x, {k: v[l] for k, v in blocks.items()}, m)
            x = ref._rms(x, outer["head"]["ln_f.weight"], eps)
            l_r, lam_r = ref.exit_head_loss(x, outer["head"], row)
            each.append(l_r)
            lams.append(lam_r)
        p, entropy = ref.exit_distribution(lams)
        total = total + jnp.mean(jnp.sum(p * jnp.stack(each), axis=0)
                                 - m["exit_entropy_beta"] * entropy)
    return total / len(ids)


def test_the_shared_weight_gradient_is_the_sum_over_unshared_copies():
    m, model, tree = _seeded(seed=11)
    ids = _ids(5)
    copies = [dict(tree["blocks"]) for _ in range(m["total_ut_steps"])]
    outer = {"embed": tree["embed"], "head": tree["head"]}
    with jax.default_matmul_precision("highest"):
        _, grads = _program_loss_and_grads(model, ids)
        per_copy = jax.grad(_unshared_loss)(copies, outer, ids, m)
    assert len(per_copy) == 4
    for name in grads:
        group, leaf, layer = runner.program_key(name)
        if group != "blocks":
            continue
        parts = [np.asarray(c[leaf][layer]) for c in per_copy]
        # no one copy's gradient is the whole: each pass adds its own
        assert all(np.abs(part).max() > 0 for part in parts), name
        want = sum(parts)
        np.testing.assert_allclose(np.asarray(grads[name]), want, rtol=2e-3,
                                   atol=2e-5 * np.abs(want).max(),
                                   err_msg=name)
        assert not np.allclose(parts[-1], want, rtol=0.05,
                               atol=0.05 * np.abs(want).max()), name


# ------------------------------------------------- the shell's old output --
def test_one_pass_without_the_post_norm_is_the_shell_as_it_was():
    """``total_ut_steps`` 1 and ``post_branch_norm`` off: logits, as every
    other family of the shell returns them, equal to the plain two-branch
    decoder computed here on the same weights."""
    paddle.seed(3)
    cfg = OuroConfig(**{**BASE, "total_ut_steps": 1,
                        "post_branch_norm": False})
    model = OuroForCausalLM(cfg)
    names = list(model.state_dict())
    assert not any("ln_1b" in n or "ln_2b" in n or "exit_gate" in n
                   for n in names)
    assert model.step_counters() == {}
    ids = _ids(6)
    with jax.default_matmul_precision("highest"):
        logits = model(paddle.to_tensor(ids))
        x = model.model.embeddings(paddle.to_tensor(ids))
        for layer in model.model.layers:
            x = x + layer.attn(layer.ln_1(x))
            x = x + layer.mlp(layer.ln_2(x))
        want = model.lm_head(model.model.ln_f(x))
        loss = model.loss(logits, paddle.to_tensor(ids))
        want_loss = F.cross_entropy(
            want[:, :-1].reshape([-1, 512]),
            paddle.to_tensor(ids[:, 1:].reshape(-1)))
    assert tuple(logits.shape) == (2, SEQ, 512)
    np.testing.assert_allclose(np.asarray(logits._data),
                               np.asarray(want._data), rtol=1e-5, atol=2e-6)
    assert float(loss._data) == pytest.approx(float(want_loss._data),
                                              rel=1e-6)


@pytest.mark.parametrize("family", ["kanana", "laguna", "evabyte",
                                    "nemotron_h"])
def test_the_options_are_off_for_the_families_that_were_there(family):
    from paddle_tpu.models import evabyte, laguna, mla_moe, nemotron_h

    model = {"kanana": lambda: mla_moe.mla_moe_tiny(),
             "laguna": lambda: laguna.laguna_tiny(),
             "evabyte": lambda: evabyte.evabyte_tiny(),
             "nemotron_h": lambda: nemotron_h.nemotron_h_tiny()}[family]()
    c = model.config
    assert (c.total_ut_steps, c.post_branch_norm) == (1, False)
    assert not hasattr(model, "exit_gate")
    assert not any("ln_1b" in n or "ln_2b" in n for n in model.state_dict())
    assert all(layer.ln_1b is None and layer.ln_2b is None
               for layer in model.model.layers)


def test_a_looped_stack_refuses_what_is_not_built():
    from paddle_tpu.models import evabyte

    class Looped(evabyte.EvaByteConfig):
        total_ut_steps = 2

    with pytest.raises(NotImplementedError):
        evabyte.EvaByteForCausalLM(Looped())


def test_out_std_counts_the_layers_held_not_the_passes():
    one = OuroConfig(**{**BASE, "total_ut_steps": 1})
    four = OuroConfig(**BASE)
    assert one.out_std == four.out_std == pytest.approx(
        0.05 / np.sqrt(2 * 3))


def test_the_published_preset_has_the_published_sizes():
    """Counted from the shapes, nothing built: 48 layers, 2.668B."""
    import inspect

    src = inspect.getsource(ouro_2_6b)
    for size in ("49152", "2048", "48", "16", "128", "5632", "1000000"):
        assert size in src
    block = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51_388_416
    assert 12 * block + 2 * 49152 * 2048 + 2048 + 2049 == 817_991_681
    tiny = ouro_tiny()
    assert tiny.config.total_ut_steps == 4 and tiny.config.post_branch_norm
    assert tuple(tiny.exit_gate.weight.shape) == (64, 1)


# ----------------------------------------------------- through TrainStep --
def _step(model, remat):
    opt = optimizer.AdamW(learning_rate=HP["learning_rate"],
                          beta1=HP["beta1"], beta2=HP["beta2"],
                          epsilon=HP["epsilon"],
                          weight_decay=HP["weight_decay"],
                          parameters=model.parameters())
    return TrainStep(model, lambda out, labels: model.loss(out, labels),
                     opt, remat=remat)


@pytest.fixture(scope="module")
def two_steps():
    """Two steps of ``TrainStep`` + AdamW with and without
    rematerialisation, and the reference's two."""
    batches = [(_ids(20), _ids(20)), (_ids(21), _ids(21))]
    out = {}
    with jax.default_matmul_precision("highest"):
        for key, remat in (("plain", False), ("remat", REMAT),
                           ("remat_all", True)):
            m, model, _ = _seeded(seed=13)
            step = _step(model, remat)
            losses, counters = [], []
            for ids, labels in batches:
                x, y = paddle.to_tensor(ids), paddle.to_tensor(labels)
                losses.append(float(step((x, y), y)._data))
                counters.append({k: np.asarray(v)
                                 for k, v in step.counters.items()})
            out[key] = dict(losses=losses, counters=counters,
                            state=step.state_dict())
        out["want"] = ref.train_reference(13, m, batches, HP, jnp.float32)
    return out


@pytest.mark.parametrize("remat", ["remat", "remat_all"])
def test_rematerialisation_changes_neither_loss_nor_update(two_steps, remat):
    plain, other = two_steps["plain"], two_steps[remat]
    assert other["losses"] == pytest.approx(plain["losses"], rel=1e-6)
    for name, a in plain["state"]["params"].items():
        np.testing.assert_allclose(
            np.asarray(other["state"]["params"][name]), np.asarray(a),
            rtol=1e-5, atol=2e-6, err_msg=name)    # a step is 1e-3
        m1 = np.asarray(plain["state"]["opt_state"][name]["moment1"])
        np.testing.assert_allclose(
            np.asarray(other["state"]["opt_state"][name]["moment1"]), m1,
            rtol=2e-4, atol=1e-5 * np.abs(m1).max(), err_msg=name)


def test_two_steps_follow_the_reference(two_steps):
    got, want = two_steps["remat"], two_steps["want"]
    assert got["losses"] == pytest.approx(want["losses"], rel=3e-5)
    assert got["losses"][1] < got["losses"][0]
    for step in range(2):
        np.testing.assert_allclose(got["counters"][step]["ouro_pass_loss"],
                                   want["pass_losses"][step], rtol=3e-5)
        np.testing.assert_allclose(got["counters"][step]["ouro_exit_mass"],
                                   want["exit_masses"][step], rtol=3e-5)
    params = {}
    for name, a in got["state"]["params"].items():
        group, leaf, layer = runner.program_key(name)
        params[(f"{group}.{leaf}", layer)] = a
    change = ref.change_norms(13, runner.model_group(BASE), jnp.float32,
                              params)
    for key, w in want["param_change_norms"].items():
        assert change[key] == pytest.approx(w, rel=2e-3), key


def test_the_stated_bfloat16_mix_stays_near_the_reference():
    """``amp.decorate`` O2: parameters, matmul operands, the residual
    stream and the gradients in bfloat16 (8 bits: a relative step of 2 **
    -8 = 0.4%); the norms' statistics, the attention's softmax, the gate's
    logit, logits' cross entropy, the exit distribution in float32.  The
    band: the loss and every pass's loss within 0.3% of the float32
    reference's ON THE SAME bfloat16 weights, every exit probability
    within 0.01, every leaf's gradient within 6% of its norm and pointing
    its way (cosine over 0.995)."""
    m, model, tree = _seeded(dtype=jnp.bfloat16)
    assert all(t._data.dtype == jnp.bfloat16
               for t in model.state_dict().values())
    ids = _ids(8)
    model(paddle.to_tensor(ids))
    counters = model.step_counters()
    loss, grads = _program_loss_and_grads(model, ids)
    tree32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        (want, aux), want_grads = jax.value_and_grad(
            _ref_loss, has_aux=True)(tree32, ids, m)
    assert loss.dtype == jnp.float32
    assert float(loss) == pytest.approx(float(want), rel=3e-3)
    np.testing.assert_allclose(np.asarray(counters["ouro_pass_loss"]),
                               np.asarray(aux[0]), rtol=3e-3)
    np.testing.assert_allclose(np.asarray(counters["ouro_exit_mass"]),
                               np.asarray(aux[1]), atol=0.01)
    for name, g in grads.items():
        assert g.dtype == jnp.bfloat16, name
        g = np.asarray(g.astype(jnp.float32)).ravel()
        w = np.asarray(_leaf(want_grads, name)).ravel()
        assert abs(np.linalg.norm(g) / np.linalg.norm(w) - 1) < 0.06, name
        assert g @ w / (np.linalg.norm(g) * np.linalg.norm(w)) > 0.995, name


# ------------------------------------------------ planted faults must show --
def _fault(monkeypatch, fault, model):
    """Break the PROGRAM (the reference stays sound)."""
    shell = moe_decoder.MoeDecoderModel
    if fault == "three_passes":
        model.config.total_ut_steps = 3
    elif fault == "beta_zero":
        model.config.exit_entropy_beta = 0.0
    elif fault == "state_before_ln_f":
        # the next pass reads what the blocks made; head and gate still
        # read the normed state
        real = moe_decoder.MoeDecoderForCausalLM.looped

        def looped(self, input_ids, labels):
            ln_f = self.model.ln_f
            self.model.ln_f = lambda x: x
            head = self.lm_head
            self.lm_head = lambda h: head(ln_f(h))
            gate_of = moe_decoder._exit_gate
            monkeypatch.setattr(moe_decoder, "_exit_gate",
                                lambda h, w, b: gate_of(ln_f(h), w, b))
            try:
                return real(self, input_ids, labels)
            finally:
                self.model.ln_f, self.lm_head = ln_f, head
                monkeypatch.setattr(moe_decoder, "_exit_gate", gate_of)
        monkeypatch.setattr(moe_decoder.MoeDecoderForCausalLM, "looped",
                            looped)
    elif fault == "earlier_passes_cut":
        # every pass reads a state whose gradient is stopped: a weight's
        # gradient is its last application's alone in each pass's loss
        real = shell.stack
        monkeypatch.setattr(
            shell, "stack",
            lambda self, x: real(self, Tensor(jax.lax.stop_gradient(
                x._data))))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["three_passes", "state_before_ln_f",
                                   "earlier_passes_cut", "beta_zero"])
def test_a_planted_fault_shows(monkeypatch, fault):
    """Each fault moves the loss or a gradient far outside the agreement
    the sound program reaches (2e-5 and 2e-3)."""
    m, model, tree = _seeded(seed=17)
    ids = _ids(9)
    _fault(monkeypatch, fault, model)
    with jax.default_matmul_precision("highest"):
        if fault == "three_passes":
            out = model(paddle.to_tensor(ids))
            assert out[0].shape[0] == 3
            return
        loss, grads = _program_loss_and_grads(model, ids)
        (want, _), want_grads = jax.value_and_grad(
            _ref_loss, has_aux=True)(tree, ids, m)
    loss_off = abs(float(loss) - float(want)) / float(want)
    grad_off = max(
        float(np.linalg.norm(np.asarray(g) - np.asarray(_leaf(want_grads,
                                                              n)))
              / np.linalg.norm(np.asarray(_leaf(want_grads, n))))
        for n, g in grads.items())
    if fault == "earlier_passes_cut":
        assert loss_off < 2e-5          # the forward is sound
    else:
        assert loss_off > 1e-3, loss_off
    assert grad_off > 0.05, grad_off
