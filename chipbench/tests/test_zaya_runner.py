"""Runner ``zaya_train`` on the CPU at a size a test run can hold: the timed
step object against the plain reference (every check of the cell), the
lower-precision controls rejected, four faults planted in the timed program
rejected each by a named limit, the counters handed to the readers, and the
new reader and metric files on what the runner hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file that
was there is edited), so the cell is built here."""

import argparse
import copy
import importlib
import json
import os
import types

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.runners import zaya_train as runner

CELL_NAME = "zaya1-8b-train-ep2.seq16384"

# every mechanism of the cell at a toy size: 4 q heads over 2 kv heads of 16
# (attention at half the hidden width), 3 layers, 4 experts held of 8 routed
# top-1 through an 8-wide router MLP, rows of 64 tokens, vocabulary 512 tied
TINY = {
    "name": "tiny-zaya", "runner": "zaya_train", "reference": "zaya",
    "dtype": "bfloat16", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"}},
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 1,
    "router_hidden_size": 8, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 512,
    "initializer_range": 0.05, "router_mlp_orthogonal": 1.0,
    "deployment": {"router_experts": 8, "expert_offset": 2},
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"]},
    "optimizer": {"learning_rate": 0.0003, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41, 42, 43 (sound; then the
    # fp8 and the int8 control at the same seeds; then the four faults at
    # seed 43): loss_gap, the larger step, 2.0e-4, 2.8e-4, 1.8e-4; 2.9e-3,
    # 1.3e-3, 2.2e-3; 1.2e-4, 3.3e-4, 1.4e-3; faults 1.9e-3, 3.5e-4, 1.0e-3,
    # 1.0e-2.  first_grad_norm_gap 0.030, 0.017, 0.085 (a router of 8 x 8
    # matrices leads; wide at this size); 0.10, 0.12, 0.26; 0.070, 0.029,
    # 0.079; faults 0.45, 2.51, 0.72, 0.82.  param_change_norm_gap 0.051,
    # 0.043, 0.050; 0.094, 0.114, 0.078; 0.061, 0.123, 0.052; faults 0.114 to
    # 0.79.  param_change_direction_gap 0.122, 0.044, 0.264; 0.68, 0.34, 0.63;
    # 0.18, 0.26, 0.22; faults 1.07 to 1.92.  expert_count_gap 0.0124,
    # 0.0020, 0.0064; 0.058, 0.040, 0.032; 0.021, 0.016, 0.0096; faults 0.032
    # to 0.71.  router_state_rms_gap 4.0e-4, 4.9e-4, 6.0e-4; 7.5e-3, 1.2e-2,
    # 7.6e-3; 3.1e-3, 3.0e-3, 2.5e-3 (THE check that holds both controls at
    # this size); faults 0.0151, 0.178 (the carried term left out), 0.0051,
    # 0.0242.  The cell's own limits were read on the chip
    "limits": {"loss_gap": 0.0015, "first_grad_norm_gap": 0.15,
               "param_change_norm_gap": 0.08,
               "param_change_direction_gap": 0.45,
               "expert_count_gap": 0.02, "router_state_rms_gap": 0.0015,
               "zero_grad_leaf_norm": 0.01, "loss_rise": 1.0},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 64}
CELL = {"name": "tiny-zaya.pretrain", "config": "tiny-zaya",
        "traffic": "tiny-pretrain", "chips": 1}
FAULTS = ("no_value_shift", "no_router_carry", "no_qk_mean",
          "conv1_depthwise")


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


def by_name(ctx):
    return {c["name"]: c for c in ctx.checks}


def plant(monkeypatch, fault):
    """Break the TIMED program; the reference stays sound.  (The builder's
    chip runs plant the same four by a script round ``chipbench.run.main``.)
    """
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe import moe_layer
    from paddle_tpu.models import zaya
    from paddle_tpu.nn import functional as F

    if fault == "no_value_shift":
        # every value head reads the position itself
        monkeypatch.setattr(F, "time_shift", lambda x, steps=1: x)
    elif fault == "no_router_carry":
        # gamma * r_prev left out: every layer's router starts from nothing
        real = moe_layer._route_state_mlp
        monkeypatch.setattr(
            moe_layer, "_route_state_mlp",
            lambda x2d, state, *rest, **kw: real(x2d, state * 0.0, *rest,
                                                 **kw))
    elif fault == "no_qk_mean":
        # the convolved latents alone go to the norm
        real = zaya._qk_mean_norm
        monkeypatch.setattr(
            zaya, "_qk_mean_norm",
            lambda q, k, q_lat, k_lat, tau, eps: real(
                q, k, q_lat * 0.0, k_lat * 0.0, tau, eps))
    elif fault == "conv1_depthwise":
        # the second convolution keeps its matrices' diagonals only
        real = F.causal_conv1d_heads
        monkeypatch.setattr(
            F, "causal_conv1d_heads",
            lambda x, w: real(x, w * jnp.eye(w.shape[-1], dtype=w.dtype)))
    else:
        raise ValueError(fault)


def test_the_sound_program_passes_every_check(sound):
    ctx, res = sound
    checks = by_name(ctx)
    assert all(c["ok"] for c in ctx.checks), \
        [c for c in ctx.checks if not c["ok"]]
    for name in ("loss_gap.step1", "loss_gap.step2", "first_grad_norm_gap",
                 "param_change_norm_gap", "param_change_direction_gap",
                 "expert_count_gap", "router_state_rms_gap",
                 "loss_rise_over_window"):
        assert name in checks, name
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0


def test_the_counters_reach_the_readers(sound):
    _, res = sound
    counts = res["counters"]["moe_tokens_per_expert"]
    rms = res["counters"][runner.STATE_RMS]
    assert len(counts) == len(res["steps"]) == len(rms)
    assert np.asarray(counts[0]).shape == (3, 4)    # layers, experts held
    # top-1, half of the router's experts held: no more than every token
    assert 0 < int(np.sum(counts[0][0])) <= 2 * 64
    assert rms[0][0] == 0.0 and min(rms[0][1:]) > 0.0


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_a_lower_precision_control_fails_a_limit(precision):
    ctx, _ = drive(42, control=precision)
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    failed = [c["name"] for c in ctx.checks
              if c["name"].startswith(f"control.{precision}.")
              and not c["ok"]]
    assert failed, f"{precision} passes every limit"


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_a_named_limit(monkeypatch, fault):
    plant(monkeypatch, fault)
    ctx, _ = drive(43)
    failed = {c["name"] for c in ctx.checks if not c["ok"]}
    assert failed & EXPECTED[fault], (fault, failed, ctx.checks)


# the limits each fault must fail (at least one of them)
EXPECTED = {
    "no_value_shift": {"first_grad_norm_gap", "param_change_direction_gap"},
    "no_router_carry": {"router_state_rms_gap"},
    "no_qk_mean": {"first_grad_norm_gap", "param_change_direction_gap"},
    "conv1_depthwise": {"first_grad_norm_gap", "param_change_direction_gap"},
}


def test_the_model_group_names_what_the_cost_functions_read():
    m = runner.model_group(copy.deepcopy(TINY))
    assert m["router_experts"] == 8 and m["num_experts"] == 4
    assert m["n_routed_experts"] == 4 and m["first_k_dense_replace"] == 0
    assert m["layer_types"] == ["full_attention"] * 3
    assert m["num_attention_heads_per_layer"] == [4, 4, 4]
    assert m["rope_theta"] == 5000000
    assert m["router_mlp_orthogonal"] == 1.0 and "router_norm_gain" not in m


# ------------------------------------- the cell's files, as the harness --
def test_the_cell_loads_by_name_and_states_its_cut():
    bench, cell, config, traffic = harness.load_cell(CELL_NAME)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-seq16384-b1")
    assert (traffic["batch"], traffic["seq"]) == (1, 16384)
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    # every published width
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) \
        == (2048, 8, 2, 128)
    assert (config["moe_intermediate_size"], config["router_hidden_size"],
            config["num_experts_per_tok"]) == (2048, 256, 1)
    assert (config["cca_time0"], config["cca_time1"],
            config["partial_rotary_factor"]) == (2, 2, 0.5)
    assert config["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert config["deployment"]["router_experts"] == 16
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 16, "vocab_size": 262272}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 8, 32784)
    m = runner.model_group(config)
    runner.model_config(m)          # the program takes every key
    assert set(config["limits"]) >= {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "param_change_direction_gap", "expert_count_gap",
        "router_state_rms_gap", "loss_rise"}


def test_the_cell_reports_the_new_and_the_shared_metrics():
    bench, cell, _, _ = harness.load_cell(CELL_NAME)
    names = harness.cell_metrics(bench, cell, "per_layer")
    for name in ("device_ms_per_step.cca_mix",
                 "device_ms_per_step.residual_scale", "mfu_active_pct.zaya",
                 "flash_attn_gqa_roofline_pct.zaya",
                 "device_ms_per_step.moe_router",
                 "device_ms_per_step.moe_dispatch",
                 "device_ms_per_step.moe_experts",
                 "moe_grouped_matmul_roofline_pct",
                 "moe_expert_load_max_over_mean",
                 "device_ms_per_step.unscoped", "peak_hbm_gb.train"):
        assert name in names, name
        spec = json.load(open(os.path.join(
            harness.HERE, "metrics", name + ".json")))
        importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert harness.cell_metrics(bench, cell, "end_to_end") \
        == ["train_tokens_per_s_per_chip", "setup_s"]


def _env(model, counts, rate=40000.0, batch=1, seq=64):
    steps = [(0.0, 1.0, batch * seq)] * len(counts or [0])
    ctx = types.SimpleNamespace(note=lambda text: None)
    return types.SimpleNamespace(
        ctx=ctx, config={"model": model}, traffic={"batch": batch,
                                                   "seq": seq},
        res={"counters": {"moe_tokens_per_expert": counts}}, steps=steps,
        end_to_end={"train_tokens_per_s_per_chip": rate},
        peaks={"bf16_flops": 197e12})


def test_mfu_active_zaya_counts_what_a_token_multiplies_here():
    from chipbench.readers import mfu_active_zaya as reader

    m = runner.model_group(copy.deepcopy(TINY))
    h, nd, kvd, s, e = 64, 64, 32, 8, 8
    layer = (2 * h * nd + 2 * h * kvd           # q, o | k, v
             + (nd + kvd) * 2                   # depthwise taps
             + (4 + 2) * 2 * 16 * 16            # per-head taps
             + h * s + 2 * s * s + s * e        # the router
             + 0.5 * 3 * h * 32)                # half a token's expert
    want = 3 * layer + h * 512                  # and the tied head
    assert reader.active_params(m, 0.5) == pytest.approx(want)
    # 3 layers, every step half of the 64 tokens served here
    counts = [[[8, 8, 8, 8]] * 3] * 4
    got = reader.read(_env(m, counts), "train_tokens_per_s_per_chip")
    assert got == pytest.approx(100 * 6 * want * 40000.0 / 197e12)
    # nothing to read: no counter, or another family's model
    assert reader.read(_env(m, None), "train_tokens_per_s_per_chip") is None
    other = {k: v for k, v in m.items() if k != "cca_time0"}
    assert reader.read(_env(other, counts),
                       "train_tokens_per_s_per_chip") is None
