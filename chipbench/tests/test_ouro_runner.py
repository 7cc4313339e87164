"""Runner ``ouro_train`` on the CPU at a size a test run can hold: the timed
step object against the plain reference (every check of the cell), the
lower-precision controls rejected, four faults planted in the timed program
rejected each by a named limit, the counters handed to the readers, and the
new cost function, readers and metric files on what the runner hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file that
was there is edited), so the cell is built here."""

import argparse
import copy
import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench.runners import ouro_train as runner

# every mechanism of the cell at a toy size: 4 heads of 16, 2 layers, 4
# passes, a gated MLP of 96, vocabulary 512
TINY = {
    "name": "tiny-ouro", "runner": "ouro_train", "reference": "ouro",
    "dtype": "bfloat16", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 96, "total_ut_steps": 4,
    "early_exit_threshold": 1, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "vocab_size": 512, "exit_entropy_beta": 0.05, "initializer_range": 0.05,
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"]},
    "optimizer": {"learning_rate": 0.0003, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41, 42, 43 (sound; fp8 and
    # int8 controls at 42, 43): loss_gap, the larger step, 8.1e-4, 1.7e-4,
    # 1.7e-4; 5.0e-3, 6.6e-3; 1.2e-3, 5.0e-4.  first_grad_norm_gap 0.0085,
    # 0.0019, 0.0069 (the gate's weight); 0.041, 0.118; 0.0055, 0.0127.
    # param_change_norm_gap 0.0026, 0.0018, 0.0017; 0.0103, 0.0076; 0.0053,
    # 0.0039.  param_change_direction_gap 0.0101, 0.0107, 0.0114; 0.180,
    # 0.177; 0.078, 0.033.  pass_loss_gap 9.5e-4, 4.9e-4, 8.1e-4; 0.0119,
    # 0.0188; 0.0028, 0.0018.  exit_mass_gap 6.8e-4, 4.1e-4, 1.9e-4;
    # 0.0099, 0.0076; 7.2e-4, 0.0017.  At 64 wide int8 with a scale a row
    # is nearly as fine as bfloat16, so the limits lie close above the
    # sound readings; the cell's own limits were read on the chip.  The
    # loss over 2 x 64 fresh tokens a step moves -0.014 to +0.11 in a run
    "limits": {"loss_gap": 0.0025, "first_grad_norm_gap": 0.02,
               "param_change_norm_gap": 0.0035,
               "param_change_direction_gap": 0.03, "pass_loss_gap": 0.0025,
               "exit_mass_gap": 0.0015, "zero_grad_leaf_norm": 0.01,
               "loss_rise": 0.2},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 64}
CELL = {"name": "tiny-ouro.pretrain", "config": "tiny-ouro",
        "traffic": "tiny-pretrain", "chips": 1}


def drive(seed, control="", seconds=0.3, **config):
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              control=control)
    out = os.path.join(harness.ROOT, "chipbench_out", CELL["name"],
                       f"seed{seed}-trace0")
    os.makedirs(out, exist_ok=True)
    ctx = harness.Context(args, CELL, {**copy.deepcopy(TINY), **config},
                          dict(TRAFFIC), out)
    return ctx, runner.run(ctx)


@pytest.fixture(scope="module")
def sound():
    return drive(41)


def test_sound_run_passes_every_check(sound):
    ctx, res = sound
    failed = [c for c in ctx.checks if not c["ok"]]
    assert not failed, failed
    names = {c["name"] for c in ctx.checks}
    assert {"loss_gap.step1", "loss_gap.step2", "first_grad_norm_gap",
            "param_change_norm_gap", "param_change_direction_gap",
            "pass_loss_gap", "exit_mass_gap",
            "loss_rise_over_window"} <= names
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_window_counters_reach_the_readers(sound):
    from chipbench.readers import loop_exit_step

    _, res = sound
    mass = res["counters"]["ouro_exit_mass"]
    each = res["counters"]["ouro_pass_loss"]
    assert len(mass) == len(each) == len(res["steps"])
    assert all(len(m) == 4 and abs(sum(m) - 1.0) < 1e-3 for m in mass)
    assert all(5.5 < l < 7.5 for step in each for l in step)    # ln 512
    got = loop_exit_step.read(_env(sound), "ouro_exit_mass")
    assert 1.0 < got < 4.0
    by_hand = sorted(sum(r * m for r, m in enumerate(step, 1))
                     for step in mass)
    assert by_hand[0] <= got <= by_hand[-1]
    # a gate stuck shut reads R, one collapsed onto the first pass 1
    assert loop_exit_step.exit_step([0, 0, 0, 1]) == 4
    assert loop_exit_step.exit_step([1, 0, 0, 0]) == 1
    kept = sound[1].pop("counters")
    assert loop_exit_step.read(_env(sound), "ouro_exit_mass") is None
    sound[1]["counters"] = kept


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    for precision in ("fp8", "int8"):
        ctl = [c for c in ctx.checks
               if c["name"].startswith(f"control.{precision}.")]
        assert {c["name"].split(".", 2)[2] for c in ctl} >= {
            "pass_loss_gap", "exit_mass_gap", "param_change_direction_gap"}
        assert any(not c["ok"] for c in ctl), ctl


def plant(monkeypatch, fault):
    """Break the TIMED program; the reference stays sound.  (The builder's
    chip runs plant the same four by a script round ``chipbench.run.main``.)
    """
    import jax

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import moe_decoder, ouro

    shell = moe_decoder.MoeDecoderForCausalLM
    real = shell.looped
    if fault == "three_passes":
        # three passes run for four; the fourth's loss and mass are the
        # third's again, so that the counters keep their shape
        def looped(self, input_ids, labels):
            self.config.total_ut_steps -= 1
            try:
                each, p, entropy, weight = real(self, input_ids, labels)
            finally:
                self.config.total_ut_steps += 1
            again = lambda a: jax.numpy.concatenate(    # noqa: E731
                [a, a[-1:]], axis=0)
            self.pass_loss = again(self.pass_loss)
            self.exit_mass = again(self.exit_mass)
            return each, p, entropy, weight
        monkeypatch.setattr(shell, "looped", looped)
    elif fault == "state_before_ln_f":
        # the next pass reads what the blocks made; head and gate still
        # read the normed state
        gate_of = moe_decoder._exit_gate

        def looped(self, input_ids, labels):
            ln_f, head = self.model.ln_f, self.lm_head
            self.model.ln_f = lambda x: x
            self.lm_head = lambda h: head(ln_f(h))
            moe_decoder._exit_gate = lambda h, w, b: gate_of(ln_f(h), w, b)
            try:
                return real(self, input_ids, labels)
            finally:
                self.model.ln_f, self.lm_head = ln_f, head
                moe_decoder._exit_gate = gate_of
        monkeypatch.setattr(shell, "looped", looped)
    elif fault == "earlier_passes_cut":
        # a pass's loss no longer reaches the passes before it
        stack = moe_decoder.MoeDecoderModel.stack
        monkeypatch.setattr(
            moe_decoder.MoeDecoderModel, "stack",
            lambda self, x: stack(self, Tensor(jax.lax.stop_gradient(
                x._data))))
    elif fault == "beta_zero":
        init = ouro.OuroConfig.__init__

        def no_entropy(self, *args, **kw):
            init(self, *args, **{**kw, "exit_entropy_beta": 0.0})
        monkeypatch.setattr(ouro.OuroConfig, "__init__", no_entropy)
    else:
        raise ValueError(fault)


# the limit that must catch each fault (others may fail too)
CAUGHT_BY = {"three_passes": "pass_loss_gap",
             "state_before_ln_f": "pass_loss_gap",
             "earlier_passes_cut": "first_grad_norm_gap",
             "beta_zero": "first_grad_norm_gap"}


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_a_fault_planted_in_the_timed_program_is_not_correct(monkeypatch,
                                                             fault):
    plant(monkeypatch, fault)
    ctx, _ = drive(43)
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert CAUGHT_BY[fault] in failed, \
        [(c["name"], c["value"]) for c in ctx.checks]


def _env(sound, steps=None, events=(), rate=None):
    ctx, res = sound
    ctx.note = lambda text: None
    end_to_end = res["end_to_end"] if rate is None else {
        "train_tokens_per_s_per_chip": rate}
    return types.SimpleNamespace(
        ctx=ctx, res=res, config=ctx.config, traffic=ctx.traffic,
        steps=res["steps"] if steps is None else steps,
        end_to_end=end_to_end,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        traced={"devices": {0: list(events)}})


def test_the_looped_cost_counts_a_call_a_layer_and_pass(sound):
    from chipbench.kernel_costs import flash_attention, flash_attention_looped

    steps = 3
    env = _env(sound, steps=[None] * steps)
    flops, nbytes = flash_attention.call_cost(2, 64, 4, 16)
    calls = steps * 2 * 4               # steps x layers x passes
    assert flash_attention_looped.window_cost(env) == (calls * flops,
                                                       calls * nbytes)
    # the accepted cost function counts a layer once a step: a quarter
    once = flash_attention.window_cost(env)
    assert once == (calls * flops / 4, calls * nbytes / 4)
    # the cell's: ISSUE 40's 240.5 GFLOP a call
    f, _ = flash_attention.call_cost(1, 4096, 16, 128)
    assert 240.4e9 < f < 240.6e9


def test_a_quarter_of_the_calls_reads_four_times_the_share(sound):
    """The roofline share is what the window's calls NEED over the kernels'
    time: a trace that holds a quarter of the calls the cost function
    counts reads four times the share (and past 100 shows the count is too
    high)."""
    from chipbench.readers import kernel_roofline

    steps, layers, passes = 3, 2, 4

    def one_after_another(names):
        return [(name, 1e-3 * i, 1e-3 * (i + 1)) for i, name in
                enumerate(names)]

    def ev(name, n):
        return [name] * n

    def share(calls):
        events = one_after_another(
            ev("flash_attention_fwd.2 bf16[8,64,16]", calls)
            + ev("flash_attention_bwd_dq_dkv.3 bf16[8,64,16]", calls)
            + ev("fusion.9 bf16[2,64,64]", 7))
        return kernel_roofline.read(
            _env(sound, steps=[None] * steps, events=events),
            "flash_attention", "flash_attention_looped")

    whole = share(steps * layers * passes)
    assert whole > 0
    assert share(steps * layers) == pytest.approx(4 * whole)
    no_kernel = kernel_roofline.read(
        _env(sound, steps=[None] * steps,
             events=one_after_another(ev("fusion.9 bf16[2,64,64]", 7))),
        "flash_attention", "flash_attention_looped")
    assert no_kernel is None


def test_mfu_looped_counts_a_weight_once_a_pass(sound):
    from chipbench.readers import mfu_looped

    m = runner.model_group(TINY)
    block = 4 * 64 * 64 + 3 * 64 * 96
    assert mfu_looped.multiplied_per_token(m) == 4 * (2 * block + 64 * 512)
    got = mfu_looped.read(_env(sound, rate=1000.0),
                          "train_tokens_per_s_per_chip")
    assert got == pytest.approx(
        100 * 6 * 4 * (2 * block + 64 * 512) * 1000.0 / 197e12)
    # the cell: 6 x 4 x (12 x 51,380,224 + 100,663,296) a token
    with open(os.path.join(harness.HERE, "configs",
                           "ouro-2.6b-train-ut4.json")) as f:
        cell = runner.model_group(json.load(f))
    assert mfu_looped.multiplied_per_token(cell) == \
        4 * (12 * 51_380_224 + 100_663_296)
    # a program of another family hands over no such key: nothing, aloud
    # nowhere
    other = _env(sound)
    other.config = {"model": {"hidden_size": 64}}
    assert mfu_looped.read(other, "train_tokens_per_s_per_chip") is None


def test_new_metric_files_name_what_exists():
    """Each new metric: last in ``per_layer``, a file that names a reader
    that is there, the roofline a cost function that is there; and of this
    family's scopes an op belongs to ONE part."""
    import importlib

    from chipbench.readers import scope_device_ms

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "ouro-2.6b-train-ut4.seq4096"
    new = [m for m in bench["per_layer"] if m["workloads"] == [cell]]
    assert [m["name"] for m in new] == [
        "mfu_pct.ouro", "flash_attn_roofline_pct.ouro",
        "device_ms_per_step.exit_gate", "device_ms_per_step.post_norm",
        "loop_exit_step_mean"]
    assert bench["per_layer"][-5:] == new
    for m in new:
        with open(os.path.join(harness.HERE, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        assert callable(reader.read)
        if "cost" in spec["args"]:
            assert callable(importlib.import_module(
                f"chipbench.kernel_costs.{spec['args']['cost']}").window_cost)
    joined = {m["name"] for m in bench["per_layer"]
              if cell in m["workloads"]}
    assert not {"mfu_pct", "flash_attn_roofline_pct"} & joined
    assert {"device_ms_per_step.recompute", "device_ms_per_step.attention",
            "device_ms_per_step.head_loss", "peak_hbm_gb.train",
            "compiled_hbm_gb.train", "compiles_in_window.train"} <= joined
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "train_tokens_per_s_per_chip"][0]
    assert rate["workloads"][-1] == cell

    claimed = scope_device_ms.claimed_segments()
    assert {"exit_gate", "ln_1b", "ln_2b", "ln_1", "loss"} <= claimed
    # as the chip's trace reads them (PR 40)
    body = "jit(train_step)/jvp(OuroForCausalLM)/while/body/closed_call/"
    back = ("jit(train_step)/transpose(jvp(OuroForCausalLM))/while/body/"
            "closed_call/")
    paths = {
        body + "checkpoint/layers.3/ln_1b/mul": "ln_1b",
        body + "checkpoint/layers.3/ln_1/mul": "ln_1",
        body + "checkpoint/layers.0/attn/q_proj/dot_general": "attn",
        back + "checkpoint/rematted_computation/layers.0/ln_2b/mul": "ln_2b",
        body + "ln_f/mul": "ln_f",
        body + "checkpoint/exit_gate/reduce_sum": "exit_gate",
        body + "checkpoint/lm_head/dot_general": "lm_head",
        back + "checkpoint/loss/exp": "loss",
        "jit(train_step)/jvp(OuroForCausalLM)/exit_gate/reduce_sum": "exit_gate",
        "jit(train_step)/loss/closed_call/while/body/dynamic_update_slice":
            "loss",
    }
    for path, part in paths.items():
        assert scope_device_ms.part_of(path, claimed) == part, path
    own = {("fusion.1", path): 1.0 for path in paths}
    sel = lambda **kw: scope_device_ms.selected_seconds(    # noqa: E731
        own, claimed, **kw)
    assert sel(scope=["exit_gate"]) == 2.0
    assert sel(scope=["ln_1b", "ln_2b"]) == 2.0
    assert sel(scope=["ln_1", "ln_2", "ln_f"]) == 2.0
    assert sel(scope=["lm_head", "loss"]) == 3.0
    assert sel(scope=[]) == 0.0
    # what a pass's marks look like inside the scan's body
    assert sel(phase="transpose(") == 2.0
    assert sel(phase="rematted_computation") == 1.0


def test_the_cell_s_configuration_says_what_it_holds():
    """817,991,681 parameters held, counted from the sizes in the file;
    every number of the source's config under its own key unless
    ``reduced``."""
    with open(os.path.join(harness.HERE, "configs",
                           "ouro-2.6b-train-ut4.json")) as f:
        cfg = json.load(f)
    m = runner.model_group(cfg)
    h, inter = m["hidden_size"], m["intermediate_size"]
    block = 4 * h * h + 3 * h * inter + 4 * h
    total = m["num_hidden_layers"] * block + 2 * m["vocab_size"] * h \
        + h + h + 1
    assert block == 51_388_416 and total == 817_991_681
    assert "817,991,681" in cfg["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 12
    for key in ("sandwich_norm", "attention_bias", "rotary",
                "state_fed_back", "exit_gate", "objective", "weights",
                "optimizer", "sequence", "document_mask",
                "early_exit_threshold"):
        assert cfg["assumed"][key], key
    assert "out_std" in cfg["assumed"]["weights"]
    assert cfg["exit_entropy_beta"] == 0.05
    assert cfg["trainer"]["remat"] == ["flash_attention_out",
                                       "flash_attention_lse"]
    assert set(cfg["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "param_change_direction_gap", "pass_loss_gap", "exit_mass_gap",
        "zero_grad_leaf_norm", "loss_rise"}
    for key in ("learning_rate_why", "precision_stated", "limits_why",
                "memory", "trainer_why", "check_steps_why", "stands_for"):
        assert len(cfg[key]) > 40, key
