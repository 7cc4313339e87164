"""Runner ``laguna_train`` on the CPU at a size a test run can hold: the
timed step object against the plain reference (every check of the cell),
the lower-precision control rejected, a fault planted in the timed
program (the window off by one; the gate left out) rejected, the counters
handed to the readers, and the new cost functions and metric files on
what the runner hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file
that was there is edited), so the cell is built here."""

import argparse
import copy
import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench.runners import laguna_train as runner

FULL, WINDOW = "full_attention", "sliding_attention"
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000,
             "partial_rotary_factor": 1}}
# every mechanism of the cell at a toy size: layer 0 full + dense, then
# window, window, window, full with experts; 6 | 4 q heads over 2 kv heads;
# a window of 8 in 32 tokens; 16 router outputs of which 4 are held from
# expert 4 on, three a token
TINY = {
    "name": "tiny-laguna", "runner": "laguna_train", "reference": "laguna",
    "dtype": "bfloat16", "hidden_size": 64, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "layer_types": [FULL, WINDOW, WINDOW, WINDOW, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "sliding_window": 8, "rope_parameters": ROPE, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "vocab_size": 96,
    "deployment": {"router_experts": 16, "expert_offset": 4},
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"]},
    "optimizer": {"learning_rate": 0.00022, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41 and 42 (sound / fp8 /
    # int8 control): loss_gap up to 2.1e-4 / 1.5e-3, 5.9e-4 / 2.6e-4;
    # param_change_norm_gap 0.0082, 0.0040 / 0.0162, 0.0167 / 0.0088,
    # 0.0096; param_change_direction_gap 0.061, 0.074 / 0.20, 0.26 / 0.13,
    # 0.15; expert_count_gap 0.0027, 0.0060 / 0.054, 0.027 / 0.016, 0.018.
    # At 64 wide a leaf's bfloat16 gradient is as coarse as an 8-bit one
    # (first_grad_norm_gap 0.013, 0.029 / 0.029, 0.032 / 0.031, 0.039), so
    # that limit holds the planted faults alone here; the cell's own limits
    # were read on the chip
    "limits": {"loss_gap": 0.0006, "first_grad_norm_gap": 0.045,
               "param_change_norm_gap": 0.012, "loss_rise": 0.2,
               "expert_count_gap": 0.012,
               "param_change_direction_gap": 0.1},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 32}
CELL = {"name": "tiny-laguna.pretrain", "config": "tiny-laguna",
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
            "expert_count_gap", "loss_rise_over_window"} <= names
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_window_counters_reach_the_readers(sound):
    from chipbench.readers import expert_load

    _, res = sound
    counts = res["counters"]["moe_tokens_per_expert"]
    assert len(counts) == len(res["steps"])
    tokens, k = TRAFFIC["batch"] * TRAFFIC["seq"], TINY["num_experts_per_tok"]
    for step in counts:
        assert len(step) == 4 and all(len(layer) == 4 for layer in step)
        for layer in step:      # a share serves some, never more than all
            assert 0 < sum(layer) <= tokens * k
    assert 1.0 <= expert_load.read(_env(sound)) <= 4.0


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    for precision in ("fp8", "int8"):
        ctl = [c for c in ctx.checks
               if c["name"].startswith(f"control.{precision}.")]
        assert any(not c["ok"] for c in ctl), ctl


@pytest.mark.parametrize("fault", ["window_off_by_one", "gate_left_out"])
def test_a_fault_planted_in_the_timed_program_is_not_correct(monkeypatch,
                                                             fault):
    """The reference is sound; the timed program sees one key more than
    its window allows, or multiplies no gate in.  Either fails a limit."""
    from paddle_tpu.models import laguna

    if fault == "window_off_by_one":
        sound_config = runner.model_config

        def wider(m):
            cfg = sound_config(m)
            cfg.sliding_window += 1
            return cfg
        monkeypatch.setattr(runner, "model_config", wider)
    else:
        monkeypatch.setattr(laguna, "_gate", lambda out, g: out)
    ctx, _ = drive(43)
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert failed, [(c["name"], c["value"]) for c in ctx.checks]


def _env(sound, steps=None, events=()):
    ctx, res = sound
    ctx.note = lambda text: None
    return types.SimpleNamespace(
        ctx=ctx, res=res, config=ctx.config, traffic=ctx.traffic,
        steps=res["steps"] if steps is None else steps,
        end_to_end=res["end_to_end"], peaks={"bf16_flops": 197e12},
        traced={"devices": {0: list(events)}})


def test_grouped_matmul_cost_reads_this_family_s_model_group(sound):
    """``kernel_costs/moe_grouped_matmul.py`` is the accepted one: the
    runner's model group names the experts held and the dense layers as
    it reads them."""
    from chipbench.kernel_costs import moe_grouped_matmul

    env = _env(sound)
    counts = env.res["counters"]["moe_tokens_per_expert"]
    rows = sum(sum(sum(layer) for layer in step) for step in counts)
    layer_steps = len(env.steps) * 4
    env.traced = {"devices": {0:
        [("gmm.3 bf16[256,64]", 0.0, 1.0)] * (6 * layer_steps)
        + [("tgmm.1 bf16[4,64,64]", 0.0, 1.0)] * (2 * layer_steps)}}
    flops, nbytes = moe_grouped_matmul.window_cost(env)
    assert flops == 4.0 * 6.0 * 64 * 32 * rows and nbytes > 0


def test_flash_costs_count_what_a_window_needs():
    from chipbench.kernel_costs import flash_attention_gqa as cost

    # a row sees min(t + 1, window) keys
    assert cost.pairs(8192, 512) == sum(min(t + 1, 512) for t in range(8192))
    assert cost.pairs(8192) == 8192 * 8193 // 2 == cost.pairs(8192, 8192)
    (f_fwd, b_fwd), (f_bwd, b_bwd) = cost.call_costs(1, 8192, 72, 8, 128, 512)
    unit = 2.0 * 72 * cost.pairs(8192, 512) * 128
    assert f_fwd == 2 * unit and f_bwd == 5 * unit
    per_q, per_kv, stats = 8192 * 72 * 128 * 2, 8192 * 8 * 128 * 2, \
        72 * 8192 * 4
    # K and V cross HBM once a KV head, not once a q head
    assert b_fwd == 2 * per_q + 2 * per_kv + stats
    assert b_bwd == 4 * per_q + 4 * per_kv + 2 * stats
    # a window's worth of work, not half of 8192 squared
    full = cost.call_costs(1, 8192, 72, 8, 128)[0][0]
    assert 8.0 < full / f_fwd < 8.5


def test_flash_costs_count_their_calls_in_the_trace(sound):
    """Window calls and full calls are told apart by name, each kind
    costed over its own layers and head counts, and a forward that
    rematerialisation runs twice is paid for twice."""
    from chipbench.kernel_costs import (flash_attention_gqa,
                                        flash_attention_window)

    steps = 3
    ev = lambda name, n: [(name, 0.0, 1.0)] * n         # noqa: E731
    events = (ev("flash_window8_attention_fwd.2 bf16[12,32,16]", 3 * steps)
              + ev("flash_window8_attention_bwd_dq.4 bf16[12,32,16]",
                   3 * steps)
              + ev("flash_window8_attention_bwd_dkv.5 bf16[4,32,16]",
                   3 * steps)
              + ev("flash_attention_fwd.1 bf16[8,32,16]", 2 * 2 * steps)
              + ev("flash_attention_bwd_dq.7 bf16[8,32,16]", 2 * steps)
              + ev("fusion.3 bf16[2,32,64]", 9))
    env = _env(sound, steps=[None] * steps, events=events)
    call = flash_attention_gqa.call_costs
    (wf, wfb), (wb, wbb) = call(2, 32, 6, 2, 16, 8)
    assert flash_attention_window.window_cost(env) == (
        3 * steps * (wf + wb), 3 * steps * (wfb + wbb))
    (ff, ffb), (fb, fbb) = call(2, 32, 4, 2, 16)
    assert flash_attention_gqa.window_cost(env) == (
        2 * steps * (2 * ff + fb), 2 * steps * (2 * ffb + fbb))


def test_new_metric_files_name_what_exists():
    """Each new metric: a file that names a reader that is there, and the
    rooflines a cost function that is there; the three scope metrics
    select by a mark in the op's name, so they claim no part of their own
    and ``device_ms_per_step.attention`` still holds all of attention."""
    import importlib

    from chipbench.readers import scope_device_ms

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "laguna-s-2.1-train-ep32.seq8192"
    new = [m for m in bench["per_layer"] if m["workloads"] == [cell]]
    assert [m["name"] for m in new] == [
        "device_ms_per_step.attention_window",
        "device_ms_per_step.attention_full",
        "device_ms_per_step.attention_gate",
        "flash_attn_window_roofline_pct", "flash_attn_gqa_roofline_pct"]
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
    claimed = scope_device_ms.claimed_segments()
    assert "attn" in claimed
    assert not {"attn_window", "attn_full", "attn_gate"} & claimed
    own = {("fusion.1", "jit(step)/Laguna/layers.1/attn/attn_window/"
            "attn_gate/mul"): 1.0,
           ("fusion.2", "jit(step)/transpose(jvp(Laguna))/layers.4/attn/"
            "attn_full/dot"): 2.0,
           ("fusion.3", "jit(step)/Laguna/layers.1/moe/router/dot"): 4.0}
    sel = lambda **kw: scope_device_ms.selected_seconds(    # noqa: E731
        own, claimed, **kw)
    assert sel(phase="attn_window") == 1.0 and sel(phase="attn_gate") == 1.0
    assert sel(phase="attn_full") == 2.0
    assert sel(scope=["attn"]) == 3.0


def test_the_cell_s_configuration_says_what_it_holds():
    """811.0M parameters held, counted from the sizes in the file; every
    number of the source's config under its own key unless ``reduced``."""
    with open(os.path.join(harness.HERE, "configs",
                           "laguna-s-2.1-train-l5-ep32.json")) as f:
        cfg = json.load(f)
    m = runner.model_group(cfg)
    h, d, kv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    expert = 3 * h * m["moe_intermediate_size"]
    total = 2 * m["vocab_size"] * h + h
    for n, kind in zip(m["num_attention_heads_per_layer"],
                       m["mlp_layer_types"]):
        total += 2 * h * n * d + 2 * h * kv * d + h * n + 2 * h
        total += 3 * h * m["intermediate_size"] if kind == "dense" else (
            h * m["router_experts"] + m["num_experts"] * expert
            + 3 * h * m["shared_expert_intermediate_size"])
    assert total == 811_017_216
    assert "811,017,216" in cfg["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (m["router_experts"], m["num_experts"], m["n_routed_experts"],
            m["first_k_dense_replace"]) == (256, 8, 8, 1)
    assert cfg["published"]["num_experts"] == 256
    assert cfg["layer_types"] == [FULL, WINDOW, WINDOW, WINDOW, FULL]
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert cfg["rope_parameters"] == ROPE
