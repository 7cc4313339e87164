"""``correct`` has to come out FALSE for the lower-precision control and
for a timed path that is broken underneath -- shown here at a size a
test run can hold (``chipbench/rehearsal``), on the CPU.  The limits at
the cells' own sizes were read on the chip (PERF.md section 2)."""

import argparse
import json
import os

import numpy as np
import pytest

from chipbench import run as harness



def drive(workload, seed, seconds=0.3, control=""):
    """One rehearsal run, past the harness's look for a chip."""
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, rehearse=True, control=control)
    return harness.run_cell(args)


def pretrain_feed(cfg, traffic, seed):
    from chipbench.traffic_kinds import pretrain
    return pretrain.generate(traffic, seed, 1.0, cfg["model"]["vocab_size"])


def checks_of(workload, seed):
    out = os.path.join(harness.ROOT, "chipbench_out", workload,
                       f"seed{seed}-trace0", "steps.json")
    with open(out) as f:
        return {c["name"]: c for c in json.load(f)["checks"]}


def test_sound_training_run_passes_every_check():
    line = drive("tiny-train.pretrain", 31)
    assert line["checks_ok"] is True and line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from paddle_tpu.jit import TrainStep

    real = TrainStep.__call__

    def frozen(self, inputs, labels=()):
        params, opt = self._params, self._opt_state
        copy = lambda t: __import__("jax").tree_util.tree_map(  # noqa: E731
            lambda a: a.copy(), t)
        keep = (copy(params), copy(opt))
        loss = real(self, inputs, labels)
        self._params, self._opt_state = keep    # the step changed nothing
        self.sync_to_model()
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    line = drive("tiny-train.pretrain", 32)
    assert line["checks_ok"] is False
    checks = checks_of("tiny-train.pretrain", 32)
    assert not checks["param_change_norm_gap"]["ok"]


def test_a_batch_with_rows_left_out_is_not_correct(monkeypatch):
    from chipbench.runners import train

    real = train.Program.__call__

    def half(self, ids, labels):
        import paddle_tpu as paddle
        x = np.asarray(ids.numpy())
        x = np.concatenate([x[:2], x[:2]])      # rows 2 and 3 never seen
        t = paddle.to_tensor(x)
        return real(self, t, t)

    monkeypatch.setattr(train.Program, "__call__", half)
    line = drive("tiny-train.pretrain", 33)
    assert line["checks_ok"] is False
    checks = checks_of("tiny-train.pretrain", 33)
    assert not (checks["loss_gap.step1"]["ok"]
                and checks["first_grad_norm_gap"]["ok"])


@pytest.mark.parametrize("seed", (21, 22, 23))
def test_training_control_is_rejected(seed):
    """The fp8 control in the program's place, at the rehearsal size: the
    loss is the number it fails here (at 64 wide the bf16 program's own
    gradient norms are no closer to the reference than fp8's; at the
    cell's size, on the chip, the gradient norm separates them 12-fold,
    PERF.md section 2)."""
    import jax.numpy as jnp

    from chipbench.reference import gpt
    from chipbench.runners.train import noise_leaves, worst_leaf_gap

    _, _, cfg, traffic = harness.load_cell("tiny-train.pretrain",
                                           rehearse=True)
    feed = pretrain_feed(cfg, traffic, seed)
    batches = [feed(i) for i in range(cfg["check_steps"])]
    args = (seed, cfg["model"], batches, cfg["optimizer"], jnp.bfloat16)
    want = gpt.train_reference(*args)
    got = gpt.train_reference(*args, precision="fp8")
    lim = cfg["limits"]
    passed = [
        max(abs(g - w) for g, w in zip(got["losses"], want["losses"]))
        <= lim["loss_gap"],
        worst_leaf_gap(got["first_grad_norms"],
                       want["first_grad_norms"])[0]
        <= lim["first_grad_norm_gap"],
        worst_leaf_gap(got["param_change_norms"],
                       want["param_change_norms"],
                       skip=noise_leaves(want["first_grad_norms"]))[0]
        <= lim["param_change_norm_gap"]]
    assert not all(passed)


def test_the_key_bias_is_compared_apart_and_left_out_as_noise():
    """The q|k|v bias is split in thirds; the key third's reference
    gradient is all but zero, so its change says nothing and is left out
    of the parameter-change comparison -- and nothing else is."""
    import jax.numpy as jnp

    from chipbench.reference import gpt
    from chipbench.runners.train import noise_leaves, worst_leaf_gap

    _, _, cfg, traffic = harness.load_cell("tiny-train.pretrain",
                                           rehearse=True)
    feed = pretrain_feed(cfg, traffic, 41)
    want = gpt.train_reference(
        41, cfg["model"], [feed(i) for i in range(cfg["check_steps"])],
        cfg["optimizer"], jnp.bfloat16)
    grads = want["first_grad_norms"]
    layers = cfg["model"]["num_hidden_layers"]
    assert {("blocks.attn.qkv.bias[k]", l) for l in range(layers)} \
        == noise_leaves(grads)
    assert ("blocks.attn.qkv.bias[q]", 0) in grads
    assert ("blocks.attn.qkv.bias", 0) not in grads
    # a wrong update of any leaf that is compared shows, whatever the
    # left-out leaves do
    got = dict(want["param_change_norms"])
    got[("blocks.attn.qkv.bias[k]", 0)] += 1.0
    skip = noise_leaves(grads)
    assert worst_leaf_gap(got, want["param_change_norms"], skip)[0] == 0.0
    got[("blocks.mlp.fc_in.weight", 1)] *= 1.05
    gap, worst = worst_leaf_gap(got, want["param_change_norms"], skip)
    assert gap == pytest.approx(0.05, rel=1e-3)
    assert worst[0][0] == ("blocks.mlp.fc_in.weight", 1)


def test_stacked_and_per_layer_norms_agree():
    import jax.numpy as jnp

    from chipbench.reference import gpt

    _, _, cfg, _ = harness.load_cell("tiny-train.pretrain", rehearse=True)
    tree = gpt.init_params(5, cfg["model"], jnp.bfloat16)
    stacked = gpt.norms(gpt.keyed(tree))
    per_layer = gpt.norms({(f"blocks.{n}", 1): a[1]
                           for n, a in tree["blocks"].items()})
    assert per_layer and all(
        stacked[k] == pytest.approx(v, rel=1e-6) for k, v in per_layer.items())
    # the seeded weights have not changed from themselves
    change = gpt.change_norms(5, cfg["model"], jnp.bfloat16, gpt.keyed(tree))
    assert set(change) == set(stacked) and max(change.values()) == 0.0


def test_a_gradient_where_none_belongs_is_not_correct():
    """The key bias is left out of the gap comparisons, not of the check:
    a gradient there as large as a real leaf's fails
    ``zero_grad_leaf_norm``."""
    import jax.numpy as jnp

    from chipbench.reference import gpt
    from chipbench.runners import train

    _, _, cfg, traffic = harness.load_cell("tiny-train.pretrain",
                                           rehearse=True)
    feed = pretrain_feed(cfg, traffic, 42)
    want = gpt.train_reference(
        42, cfg["model"], [feed(i) for i in range(cfg["check_steps"])],
        cfg["optimizer"], jnp.bfloat16)

    class Ctx:
        checks = {}

        def limit(self, name):
            return cfg["limits"][name]

        def check(self, name, value, limit, detail=""):
            self.checks[name] = value <= limit

    sound = Ctx()
    train.compare(sound, want, want, "")
    assert all(sound.checks.values()) and "zero_grad_leaf_norm" in sound.checks
    got = dict(want, first_grad_norms=dict(want["first_grad_norms"]))
    got["first_grad_norms"][("blocks.attn.qkv.bias[k]", 1)] = \
        got["first_grad_norms"][("blocks.attn.qkv.bias[q]", 1)]
    broken = Ctx()
    broken.checks = {}
    train.compare(broken, got, want, "")
    assert not broken.checks["zero_grad_leaf_norm"]
    assert broken.checks["first_grad_norm_gap"]
