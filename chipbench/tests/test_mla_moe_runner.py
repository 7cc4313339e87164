"""Runner ``mla_moe_train`` on the CPU at a size a test run can hold: the
timed step object against the plain reference (every check of the cell),
the lower-precision control rejected, the counters handed to the readers,
and the new readers and cost functions on what the runner hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file
that was there is edited), so the cell is built here."""

import argparse
import copy
import os
import types

import pytest

from chipbench import run as harness
from chipbench.runners import mla_moe_train as runner

# every mechanism of the cell at a toy size: 16 router outputs of which 4
# are held from expert 4 on, three a token, one dense layer and two
# expert layers, rotary 8 of 24, v 16
TINY = {
    "name": "tiny-mla-moe", "runner": "mla_moe_train",
    "reference": "mla_moe", "dtype": "bfloat16",
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_interleave": True, "vocab_size": 96,
    "max_position_embeddings": 64,
    "deployment": {"router_experts": 16, "expert_offset": 4},
    "trainer": {"remat": True},
    "optimizer": {"learning_rate": 0.00022, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41 and 42 (sound / fp8
    # control): loss_gap 1.7e-4 / 1.2e-3 and 2.8e-3; first_grad_norm_gap
    # 0.0034 / 0.017; param_change_norm_gap 0.0010 / 0.017;
    # expert_count_gap 0 / 0.047.  At 64 wide an int8 row is as fine as a
    # bfloat16 one (0.0028, 0.0027, 2.3e-4), so only fp8 is held to fail
    # here; the cell's own limits were read on the chip
    "limits": {"loss_gap": 0.0006, "first_grad_norm_gap": 0.01,
               "param_change_norm_gap": 0.006, "loss_rise": 0.2,
               "expert_count_gap": 0.03,
               "param_change_direction_gap": 0.5},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 32}
CELL = {"name": "tiny-mla-moe.pretrain", "config": "tiny-mla-moe",
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
            "expert_count_gap",
            "loss_rise_over_window"} <= names
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_window_counters_reach_the_readers(sound):
    _, res = sound
    counts = res["counters"]["moe_tokens_per_expert"]
    assert len(counts) == len(res["steps"])
    tokens, k = TRAFFIC["batch"] * TRAFFIC["seq"], TINY["num_experts_per_tok"]
    for step in counts:
        assert len(step) == 2 and all(len(layer) == 4 for layer in step)
        for layer in step:      # a share serves some, never more than all
            assert 0 < sum(layer) <= tokens * k


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    ctl = [c for c in ctx.checks if c["name"].startswith("control.fp8.")]
    assert any(not c["ok"] for c in ctl), ctl
    assert any(c["name"].startswith("control.int8.") for c in ctx.checks)


def test_an_update_in_the_wrong_direction_is_not_correct(monkeypatch):
    """AdamW stepping UP the gradient, planted in the timed program.  After
    one step the change has the right norm in every leaf, so every norm
    passes it, and fresh tokens every step hide the loss's rise; the
    direction check reads close to 2 and the run is not correct."""
    from chipbench.runners import train

    sound_optimizer = train.optimizer_for

    def uphill(ctx, model):
        hp = ctx.config["optimizer"]
        ctx.config["optimizer"] = {**hp,
                                   "learning_rate": -hp["learning_rate"]}
        try:
            return sound_optimizer(ctx, model)
        finally:
            ctx.config["optimizer"] = hp

    monkeypatch.setattr(train, "optimizer_for", uphill)
    ctx, _ = drive(43, check_steps=1)
    checks = {c["name"]: c for c in ctx.checks}
    wrong = checks.pop("param_change_direction_gap")
    assert all(c["ok"] for c in checks.values()), checks
    assert not wrong["ok"] and wrong["value"] > 1.8


def _env(sound, steps=None):
    ctx, res = sound
    ctx.note = lambda text: None
    return types.SimpleNamespace(
        ctx=ctx, res=res, config=ctx.config, traffic=ctx.traffic,
        steps=res["steps"] if steps is None else steps,
        end_to_end=res["end_to_end"], peaks={"bf16_flops": 197e12})


def test_readers_read_the_counters(sound):
    from chipbench.kernel_costs import moe_grouped_matmul
    from chipbench.readers import expert_load, mfu_active

    env = _env(sound)
    load = expert_load.read(env)
    assert 1.0 <= load <= 4.0            # 4 experts held: 4.0 is all on one
    counts = env.res["counters"]["moe_tokens_per_expert"]
    rows = sum(sum(sum(layer) for layer in step) for step in counts)
    # the passes are COUNTED in the trace: two calls a pass; a layer step
    # with its forward run twice is 6 gmm + 2 tgmm calls, one that kept the
    # experts' results 4 + 2
    layer_steps = len(env.steps) * 2
    for n_gmm, n_tgmm, passes in ((6, 2, 4.0), (4, 2, 3.0)):
        events = [("gmm.3 bf16[384,64]", 0.0, 1.0)] * (n_gmm * layer_steps) \
            + [("tgmm.1 bf16[4,64,64]", 0.0, 1.0)] * (n_tgmm * layer_steps) \
            + [("fusion.9 bf16[2,32]", 0.0, 1.0)]
        env.traced = {"devices": {0: events}}
        flops, nbytes = moe_grouped_matmul.window_cost(env)
        assert flops == passes * 6.0 * 64 * 32 * rows and nbytes > 0
    m = env.config["model"]
    tokens = TRAFFIC["batch"] * TRAFFIC["seq"]
    per_token = rows / (len(counts) * 2 * tokens)
    want = 100.0 * 6.0 * mfu_active.active_params(m, per_token) \
        * env.end_to_end["train_tokens_per_s_per_chip"] / 197e12
    assert mfu_active.read(env, "train_tokens_per_s_per_chip") == \
        pytest.approx(want)


def test_readers_return_nothing_without_counters(sound):
    """A program that hands over no counter (the parent's): the metric is
    left out, nothing raises."""
    from chipbench.readers import expert_load, mfu_active

    env = _env(sound)
    env.res = {k: v for k, v in env.res.items() if k != "counters"}
    assert expert_load.read(env) is None
    assert mfu_active.read(env, "train_tokens_per_s_per_chip") is None


def test_active_parameters_of_the_cell():
    """687.5M held, 294.9M active a token at the expected 0.75 assignments
    served here (ISSUE 26's count)."""
    import json

    from chipbench.readers import mfu_active

    with open(os.path.join(harness.HERE, "configs",
                           "kanana-2-30b-a3b-train-l6-ep8.json")) as f:
        m = runner.model_group(json.load(f))
    assert mfu_active.active_params(m, 0.75) == pytest.approx(294.88e6,
                                                              rel=1e-3)
    held = mfu_active.active_params(m, m["n_routed_experts"]) \
        + m["vocab_size"] * m["hidden_size"]      # + the embedding
    assert held == pytest.approx(687.5e6, rel=1e-3)


def test_flash_mla_cost_counts_seven_matmuls():
    from chipbench.kernel_costs import flash_attention_mla as cost

    (f_fwd, b_fwd), (f_bwd, b_bwd) = cost.call_costs(2, 8192, 32, 192, 128)
    unit = 2.0 * 2 * 32 * (8192 * 8192 / 2.0)
    assert f_fwd == unit * (192 + 128)
    assert f_bwd == unit * (3 * 192 + 2 * 128)
    assert b_fwd < b_bwd
