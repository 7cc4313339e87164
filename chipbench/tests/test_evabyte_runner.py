"""Runner ``evabyte_train`` on the CPU at a size a test run can hold: the
timed step object against the plain reference (every check of the cell),
the lower-precision controls rejected, two faults planted in the timed
program (the summaries left out; the last half of the next-byte heads'
losses dropped) rejected, the counters handed to the readers, and the new
cost function, reader and metric files on what the runner hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file that
was there is edited), so the cell is built here."""

import argparse
import copy
import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench.runners import evabyte_train as runner

# every mechanism of the cell at a toy size: 4 heads of 16, windows of 32
# in chunks of 4, four windows a row, 2 layers, 4 next-byte heads
TINY = {
    "name": "tiny-evabyte", "runner": "evabyte_train",
    "reference": "evabyte", "dtype": "bfloat16", "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "window_size": 32,
    "chunk_size": 4, "num_pred_heads": 4, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "init_std": 0.05, "vocab_size": 320,
    "trainer": {"remat": ["eva_attention_out", "eva_attention_lse"]},
    "optimizer": {"learning_rate": 0.00022, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41, 42, 43 (sound; fp8 and
    # int8 controls at 42, 43; at 43 the summaries left out | four heads'
    # losses dropped): loss_gap, the larger step, 4.6e-5, 1.1e-4, 8.7e-5;
    # 2.4e-3, 9.7e-4; 1.3e-4, 4.0e-4; 5.0e-3 | 2.9.  first_grad_norm_gap
    # 0.0018, 0.0022, 0.0021; 0.0157, 0.0106; 0.0031, 0.0036; 0.37 | 0.40.
    # param_change_norm_gap 0.00125, 0.00137, 0.00125; 0.0042, 0.0072;
    # 0.0029, 0.0031; 0.071 | 0.30.  At 64 wide int8 with a scale a row is
    # nearly as fine as bfloat16, so the limits lie close above the sound
    # readings; the cell's own limits were read on the chip
    "limits": {"loss_gap": 0.0003, "first_grad_norm_gap": 0.0027,
               "param_change_norm_gap": 0.002, "loss_rise": 0.2},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 128}
CELL = {"name": "tiny-evabyte.pretrain", "config": "tiny-evabyte",
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
            "param_change_norm_gap", "loss_rise_over_window"} <= names
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_window_counters_reach_the_readers(sound):
    from chipbench.readers import counter_ratio

    _, res = sound
    scored = res["counters"]["eva_pairs_scored"]
    needed = res["counters"]["eva_pairs_needed"]
    assert len(scored) == len(needed) == len(res["steps"])
    # off the chip the dense composition runs: 128 x (128 + 32) pairs a
    # row, head and layer, of which the mask holds 4 x 528 + 32 x 8 x 6
    assert scored[0] == [128 * 160] * 2 and needed[0] == [3648] * 2
    ratio = counter_ratio.read(_env(sound), "eva_pairs_scored",
                               "eva_pairs_needed")
    assert ratio == pytest.approx(128 * 160 / 3648)
    assert ratio >= 5           # a fallback is not silent
    sound[1]["counters"].pop("eva_pairs_needed")
    assert counter_ratio.read(_env(sound), "eva_pairs_scored",
                              "eva_pairs_needed") is None
    sound[1]["counters"]["eva_pairs_needed"] = needed


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    for precision in ("fp8", "int8"):
        ctl = [c for c in ctx.checks
               if c["name"].startswith(f"control.{precision}.")]
        assert any(not c["ok"] for c in ctl), ctl


def plant(monkeypatch, fault):
    """Break the TIMED program; the reference stays sound.  (The builder's
    chip runs plant the same two by a script round ``chipbench.run.main``.)"""
    from paddle_tpu.models import moe_decoder
    from paddle_tpu.ops import pallas as pk

    if fault == "no_summaries":
        # R(t) emptied: every window folded into the batch, where it is
        # the first window of its row and has no earlier one
        real = pk.eva_attention

        def exact_only(q, k, v, kt, vt, window, chunk):
            b, t, n, d = q.shape

            def fold(x, rows):
                return x.reshape(b * t // window, rows, n, d)

            return real(fold(q, window), fold(k, window), fold(v, window),
                        fold(kt, window // chunk), fold(vt, window // chunk),
                        window, chunk).reshape(b, t, n, d)
        monkeypatch.setattr(pk, "eva_attention", exact_only)
    elif fault == "four_heads":
        whole = moe_decoder.MoeDecoderForCausalLM.multi_head_loss

        def half(self, logits, labels):
            # the mean over ALL the heads, the last half of them zero
            heads = logits.shape[2]
            return whole(self, logits[:, :, :heads // 2], labels) * 0.5
        monkeypatch.setattr(moe_decoder.MoeDecoderForCausalLM,
                            "multi_head_loss", half)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["no_summaries", "four_heads"])
def test_a_fault_planted_in_the_timed_program_is_not_correct(monkeypatch,
                                                             fault):
    plant(monkeypatch, fault)
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


def test_eva_cost_counts_what_the_mask_needs():
    from chipbench.kernel_costs import eva_attention as cost

    assert cost.pairs(16384, 2048, 16) == (16_785_408, 7_340_032)
    # by hand: query t of window w sees t - w W + 1 keys and w W / C
    # summaries
    w, c, t = 32, 4, 128
    assert cost.pairs(t, w, c) == (
        sum(q % w + 1 for q in range(t)),
        sum((q // w) * (w // c) for q in range(t)))
    (f_flop, f_bytes), (b_flop, b_bytes) = cost.call_costs(
        1, 16384, 32, 128, 2048, 16)
    unit = 2.0 * 32 * 24_125_440 * 128
    assert f_flop == 2 * unit and b_flop == 5 * unit
    per_row, stats = 16384 * 32 * 128 * 2, 32 * 16384 * 4
    assert f_bytes == 4 * per_row + 2 * per_row // 16 + stats
    assert b_bytes == 8 * per_row + 4 * per_row // 16 + 2 * stats
    # four layers, forward and backward: the 5.5 TFLOP of ISSUE 32
    assert 5.4e12 < 4 * (f_flop + b_flop) < 5.6e12


def test_eva_cost_counts_its_calls_in_the_trace(sound):
    """Forward calls and backward calls are told apart by name, a forward
    that rematerialisation runs twice is paid for twice, and the two
    dk/dv kernels are no calls of their own."""
    from chipbench.kernel_costs import eva_attention as cost

    steps, layers = 3, 2
    ev = lambda name, n: [(name, 0.0, 1.0)] * n         # noqa: E731
    events = (ev("eva_attention_fwd.2 bf16[32,32,16]", 2 * layers * steps)
              + ev("eva_attention_bwd_dq.4 bf16[32,32,16]", layers * steps)
              + ev("eva_attention_bwd_dkv.5 bf16[32,32,16]", layers * steps)
              + ev("eva_attention_bwd_dkv_summaries.6 bf16[8,32,16]",
                   layers * steps)
              + ev("fusion.3 bf16[2,128,64]", 9))
    env = _env(sound, steps=[None] * steps, events=events)
    (ff, fb), (bf, bb) = cost.call_costs(2, 128, 4, 16, 32, 4)
    assert cost.window_cost(env) == (
        layers * steps * (2 * ff + bf), layers * steps * (2 * fb + bb))


def test_new_metric_files_name_what_exists():
    """Each new metric: a file that names a reader that is there, and the
    roofline a cost function that is there; the two scope metrics select
    by a mark in the op's name, so they claim no part of their own and
    ``device_ms_per_step.attention`` still holds all of attention."""
    import importlib

    from chipbench.readers import scope_device_ms

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "evabyte-6.5b-train.seq16384"
    new = [m for m in bench["per_layer"] if m["workloads"] == [cell]]
    assert [m["name"] for m in new] == [
        "device_ms_per_step.eva_prep", "device_ms_per_step.eva_agg",
        "eva_attn_roofline_pct", "eva_pairs_scored_over_needed"]
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
            "peak_hbm_gb.train", "compiles_in_window.train"} <= joined
    claimed = scope_device_ms.claimed_segments()
    assert "attn" in claimed and not {"eva_prep", "eva_agg"} & claimed
    own = {("fusion.1", "jit(step)/EvaByte/layers.1/attn/eva_prep/mul"): 1.0,
           ("eva_attention_bwd_dq.2", "jit(step)/transpose(jvp(EvaByte))/"
            "layers.3/attn/eva_agg/pallas_call"): 2.0,
           ("fusion.3", "jit(step)/EvaByte/layers.1/mlp/dot"): 4.0}
    sel = lambda **kw: scope_device_ms.selected_seconds(    # noqa: E731
        own, claimed, **kw)
    assert sel(phase="eva_prep") == 1.0 and sel(phase="eva_agg") == 2.0
    assert sel(scope=["attn"]) == 3.0


def test_the_cell_s_configuration_says_what_it_holds():
    """821.4M parameters held, counted from the sizes in the file; every
    number of the source's config under its own key unless ``reduced``."""
    with open(os.path.join(harness.HERE, "configs",
                           "evabyte-6.5b-train-l4.json")) as f:
        cfg = json.load(f)
    m = runner.model_group(cfg)
    h, n = m["hidden_size"], m["num_attention_heads"]
    layer = 4 * h * h + 3 * h * m["intermediate_size"] + 2 * h \
        + 2 * n * (h // n)
    total = m["num_hidden_layers"] * layer + m["vocab_size"] * h \
        + h * m["num_pred_heads"] * m["vocab_size"] + h
    assert layer == 202_391_552 and total == 821_366_784
    assert "821,366,784" in cfg["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 32
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 4
    for key in ("scale_in_pooling", "pooling_vectors", "rotary", "windows",
                "norm_statistics", "mixedp_attn", "head_loss_weights",
                "document_mask", "weights", "optimizer", "sequence"):
        assert cfg["assumed"][key], key
    assert cfg["trainer"]["remat"] == ["eva_attention_out",
                                       "eva_attention_lse"]
