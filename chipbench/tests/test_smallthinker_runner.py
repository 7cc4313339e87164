"""Runner ``smallthinker_train`` on the CPU at a size a test run can hold: the
timed step object against the plain reference (every check of the cell), the
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
from chipbench.runners import smallthinker_train as runner

CELL_NAME = "smallthinker-21b-a3b-train-ep4.seq16384"

# every mechanism of the cell at a toy size: one period (full without a
# position encoding, three window layers of 16 keys with the rotation), 6 q
# heads over 2 kv heads of 16 (a group of 3), 4 experts held of 16 routed
# top-3 on the block's input, rows of 64 tokens, vocabulary 512 untied
TINY = {
    "name": "tiny-smallthinker", "runner": "smallthinker_train",
    "reference": "smallthinker", "dtype": "bfloat16", "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 16, "rope_theta": 1500000, "rope_scaling": None,
    "max_position_embeddings": 16384, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "vocab_size": 512,
    "initializer_range": 0.05, "embedding_range": 1.0,
    "deployment": {"router_experts": 16, "expert_offset": 4},
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"]},
    "optimizer": {"learning_rate": 0.0003, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41, 42, 43 (sound; then the
    # fp8 and the int8 control at the same seeds; then the four faults at
    # seed 43, in FAULTS' order): loss_gap, the larger step, 4.1e-4, 1.5e-4,
    # 1.4e-4; 3.8e-4, 1.1e-3, 1.7e-3; 6.1e-4, 1.0e-3, 5.2e-4; faults 1.1e-4,
    # 9.7e-5, 5.0e-4, 1.6e-4 (no fault shows in a loss of 6.3).
    # first_grad_norm_gap 0.0033, 0.0018, 0.0039; 0.021, 0.018, 0.022; 0.020,
    # 0.0068, 0.016; faults 0.025, 0.031, 0.023, 0.271.
    # param_change_norm_gap 0.0019, 0.0067, 0.0070; 0.025, 0.010, 0.0085;
    # 0.0078, 0.0048, 0.0031; faults 0.0099, 0.0088, 0.0153, 0.0098.
    # param_change_direction_gap 0.0079, 0.0075, 0.033; 0.131, 0.105, 0.142;
    # 0.182, 0.029, 0.032; faults 0.223, 0.286, 0.104, 0.349.
    # expert_count_gap 0.0014, 0.0013, 0.0027; 0.0154, 0.0146, 0.0106;
    # 0.0066, 0.0053, 0.0040; faults 0.0249 (the router fed the normed
    # stream: with unit embedding rows that stream points where the block's
    # input points, so a fortieth of the assignments move, ten times the
    # sound reading, and not all of them), 0.0053, 0.0053, 0.0039.
    # window_edge_gap 0.0080, 0.0101, 0.0116; faults 0.0116 but the window
    # one key too wide, 4.89 (THE check that names that fault: no other
    # number sees one key in sixteen, let alone in 4,096).  The cell's own
    # limits were read on the chip
    "limits": {"loss_gap": 0.0015, "first_grad_norm_gap": 0.0065,
               "param_change_norm_gap": 0.02,
               "param_change_direction_gap": 0.07,
               "expert_count_gap": 0.0035, "window_edge_gap": 0.1,
               "zero_grad_leaf_norm": 0.01, "loss_rise": 1.0},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 64}
CELL = {"name": "tiny-smallthinker.pretrain", "config": "tiny-smallthinker",
        "traffic": "tiny-pretrain", "chips": 1}
FAULTS = ("router_reads_normed", "rope_on_full", "window_plus_one",
          "silu_body")


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
    from paddle_tpu.incubate.distributed.models.moe import (DroplessMoELayer,
                                                            dropless)
    from paddle_tpu.models import laguna
    from paddle_tpu.nn import functional as F

    if fault == "router_reads_normed":
        # the router fed what the experts read: the post-attention normed
        # stream, as in every other family
        real = DroplessMoELayer.forward
        monkeypatch.setattr(
            DroplessMoELayer, "forward",
            lambda self, x, router_state=None, router_input=None: real(
                self, x, router_state))
    elif fault == "rope_on_full":
        # the full-attention layer rotated like the window layers
        real = laguna.rope_tables
        monkeypatch.setattr(
            laguna, "rope_tables",
            lambda head_dim, seq, params, positions=None: real(
                head_dim, seq, params or {"rope_theta": 1500000}, positions))
    elif fault == "window_plus_one":
        # a window layer's query sees one key more: 0 <= t - j <= window
        real = F.scaled_dot_product_attention

        def one_wider(q, k, v, **kw):
            if kw.get("window") is not None:
                kw["window"] += 1
            return real(q, k, v, **kw)

        monkeypatch.setattr(F, "scaled_dot_product_attention", one_wider)
    elif fault == "silu_body":
        # SwiGLU experts in ReGLU's place
        monkeypatch.setitem(dropless.BODIES, "reglu",
                            dropless.BODIES["swiglu"])
    else:
        raise ValueError(fault)


def test_the_sound_program_passes_every_check(sound):
    ctx, res = sound
    checks = by_name(ctx)
    assert all(c["ok"] for c in ctx.checks), \
        [c for c in ctx.checks if not c["ok"]]
    for name in ("loss_gap.step1", "loss_gap.step2", "first_grad_norm_gap",
                 "param_change_norm_gap", "param_change_direction_gap",
                 "expert_count_gap", "window_edge_gap",
                 "loss_rise_over_window"):
        assert name in checks, name
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0


def test_the_counters_reach_the_readers(sound):
    from chipbench.readers import counter_over_tokens

    _, res = sound
    counts = res["counters"]["moe_tokens_per_expert"]
    unserved = res["counters"][runner.UNSERVED]
    assert len(counts) == len(res["steps"]) == len(unserved)
    assert np.asarray(counts[0]).shape == (4, 4)    # layers, experts held
    # top-3 over 16 with 4 held: three quarters of an assignment a token
    assert 0 < int(np.sum(counts[0][0])) < 3 * 2 * 64
    env = types.SimpleNamespace(res=res, traffic=TRAFFIC)
    share = counter_over_tokens.read(env, runner.UNSERVED)
    # C(12, 3) / C(16, 3) = 0.39 of the tokens choose none of the four held
    assert 0.2 < share < 0.6
    assert counter_over_tokens.read(env, "no_such_counter") is None


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
    "router_reads_normed": {"expert_count_gap"},
    "rope_on_full": {"first_grad_norm_gap", "param_change_direction_gap"},
    "window_plus_one": {"window_edge_gap"},
    "silu_body": {"first_grad_norm_gap", "param_change_direction_gap"},
}


def test_the_model_group_names_what_the_cost_functions_read():
    m = runner.model_group(copy.deepcopy(TINY))
    assert m["router_experts"] == 16 and m["moe_num_primary_experts"] == 4
    assert m["n_routed_experts"] == 4 and m["first_k_dense_replace"] == 0
    assert m["moe_intermediate_size"] == 32
    assert m["num_experts_per_tok"] == 3
    assert m["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3
    assert m["num_attention_heads_per_layer"] == [6, 6, 6, 6]
    assert m["sliding_window"] == 16 and m["embedding_range"] == 1.0


# ------------------------------------- the cell's files, as the harness --
def test_the_cell_loads_by_name_and_states_its_cut():
    bench, cell, config, traffic = harness.load_cell(CELL_NAME)
    assert (cell["chips"], cell["traffic"]) == (1, "pretrain-seq16384-b1")
    assert (traffic["batch"], traffic["seq"]) == (1, 16384)
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert sorted(entry["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200 \
        and len(entry["why"]) <= 200
    # every published width
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) \
        == (2560, 28, 4, 128)
    assert (config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"]) == (768, 6)
    assert (config["sliding_window_size"], config["rope_theta"],
            config["max_position_embeddings"]) == (4096, 1500000, 16384)
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == [0, 1, 1, 1]
    assert config["deployment"]["router_experts"] == 64
    assert {k: config["published"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)
    # the catalog row's numbers, but for the three that are cut
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        row = next(r for r in rows
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"] and not isinstance(value, list):
                assert config[key] == value, key
    m = runner.model_group(config)
    runner.model_config(m)          # the program takes every key
    assert set(config["limits"]) >= {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "param_change_direction_gap", "expert_count_gap", "window_edge_gap",
        "loss_rise"}


def test_the_cell_reports_the_new_and_the_shared_metrics():
    bench, cell, _, _ = harness.load_cell(CELL_NAME)
    names = harness.cell_metrics(bench, cell, "per_layer")
    for name in ("mfu_active_pct.smallthinker", "device_ms_per_step.moe_body",
                 "moe_unserved_token_share", "device_ms_per_step.moe_router",
                 "device_ms_per_step.moe_dispatch",
                 "device_ms_per_step.moe_experts",
                 "moe_grouped_matmul_roofline_pct",
                 "moe_expert_load_max_over_mean",
                 "device_ms_per_step.attention_window",
                 "device_ms_per_step.attention_full",
                 "flash_attn_window_roofline_pct",
                 "flash_attn_gqa_roofline_pct",
                 "device_ms_per_step.recompute",
                 "device_ms_per_step.unscoped", "peak_hbm_gb.train"):
        assert name in names, name
        spec = json.load(open(os.path.join(
            harness.HERE, "metrics", name + ".json")))
        importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "mfu_active_pct.smallthinker", "device_ms_per_step.moe_body",
        "moe_unserved_token_share"]
    assert harness.cell_metrics(bench, cell, "end_to_end") \
        == ["train_tokens_per_s_per_chip", "setup_s"]
    # the body's scope is a PART inside ``experts``: it claims no segment
    from chipbench.readers import scope_device_ms
    assert "expert_body" not in scope_device_ms.claimed_segments()
    own = {("fusion.1", "jit(f)/layers.0/moe/experts/expert_body/mul"): 2.0,
           ("gmm.1", "jit(f)/layers.0/moe/experts/gmm"): 5.0,
           ("fusion.2", "jit(f)/layers.0/attn/attn_full/dot"): 1.0}
    claimed = scope_device_ms.claimed_segments()
    assert scope_device_ms.selected_seconds(
        own, claimed, phase="expert_body") == 2.0
    assert scope_device_ms.selected_seconds(
        own, claimed, scope=["experts"]) == 7.0


def _env(model, counts, rate=40000.0, batch=1, seq=64):
    steps = [(0.0, 1.0, batch * seq)] * len(counts or [0])
    ctx = types.SimpleNamespace(note=lambda text: None)
    return types.SimpleNamespace(
        ctx=ctx, config={"model": model}, traffic={"batch": batch,
                                                   "seq": seq},
        res={"counters": {"moe_tokens_per_expert": counts}}, steps=steps,
        end_to_end={"train_tokens_per_s_per_chip": rate},
        peaks={"bf16_flops": 197e12})


def test_mfu_active_smallthinker_counts_matrices_and_the_pairs_needed():
    from chipbench.readers import mfu_active_smallthinker as reader

    m = runner.model_group(copy.deepcopy(TINY))
    h, nd, kvd, e = 64, 96, 32, 16
    layer = (2 * h * nd + 2 * h * kvd           # q, o | k, v
             + h * e                            # the router, all 16 wide
             + 0.75 * 3 * h * 32)               # 0.75 assignments a token
    want = 4 * layer + h * 512                  # and the untied head
    assert reader.active_params(m, 0.75) == pytest.approx(want)
    # a row of 64: the full layer holds 64 * 65 / 2 pairs, a window of 16
    # 16 * 17 / 2 + 48 * 16
    pairs = 64 * 65 // 2 + 3 * (16 * 17 // 2 + 48 * 16)
    attention = 12 * 6 * 16 * pairs / 64
    assert reader.attention_flops_per_token(m, 64) \
        == pytest.approx(attention)
    # 4 layers, every step 48 of the 64 tokens' 192 assignments served here
    counts = [[[12, 12, 12, 12]] * 4] * 3
    got = reader.read(_env(m, counts), "train_tokens_per_s_per_chip")
    assert got == pytest.approx(
        100 * (6 * want + attention) * 40000.0 / 197e12)
    # nothing to read: no counter, or another family's model
    assert reader.read(_env(m, None), "train_tokens_per_s_per_chip") is None
    other = {k: v for k, v in m.items() if k != "moe_ffn_hidden_size"}
    assert reader.read(_env(other, counts),
                       "train_tokens_per_s_per_chip") is None
