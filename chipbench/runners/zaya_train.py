"""Runner ``zaya_train``: an expert decoder whose attention lives in a
compressed, convolved latent and whose top-1 router is an MLP over a state
carried from layer to layer (``paddle_tpu.models.zaya``), through
``amp.decorate`` O2 -> ``jit.TrainStep`` + ``AdamW`` on one chip.  Everything
but the program is ``runners/train.py``'s ``run``; how a layer's leaves are
named and read is ``runners/laguna_train.py``'s, the seeded weights are
loaded as ``runners/nemotron_h_train.py`` loads them (the selection bias is
a zero buffer on both sides), and what is done with the experts' counter and
with the direction of the parameters' change is ``runners/mla_moe_train
.py``'s, all imported (``README.zaya.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run; ``deployment`` says what the chip holds of a layer
(``router_experts``: the router's published width, ``expert_offset``: the
first expert held) and ``trainer`` how the step is built.
:func:`model_group` gathers them into the ``model`` group ``train.run``, the
reference and the readers take.

Beside the loss the step hands back the experts' counters and
``router_state_rms`` (float32 ``[layers]``: the RMS of the router state
entering each layer); the first steps' are held to the reference's own
(``router_state_rms_gap``).
"""

import functools

import numpy as np

from ..kernel_costs.flash_attention_gqa import FULL
from . import laguna_train as by_group
from . import mla_moe_train as moe
from . import nemotron_h_train as with_bias
from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "cca_time0", "cca_time1",
    "partial_rotary_factor", "rope_parameters", "moe_intermediate_size",
    "num_experts", "num_experts_per_tok", "router_hidden_size",
    "rms_norm_eps", "tie_word_embeddings", "vocab_size")
# how the SEEDED weights are drawn (the reference module's to read)
SEEDING_KEYS = ("embedding_range", "final_norm_gain", "router_mlp_orthogonal",
                "router_bias_range",
                "router_norm_gain")
STATE_RMS = "router_state_rms"


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, and under the names the accepted cost functions read:
    ``n_routed_experts`` (the experts held) and ``first_k_dense_replace``
    (the dense layers: none) for ``kernel_costs/moe_grouped_matmul.py``,
    ``layer_types`` (every layer a full-attention layer; the source's own
    ``layer_types`` say ``hybrid`` forty times and shape nothing) and
    ``num_attention_heads_per_layer`` for ``kernel_costs/
    flash_attention_gqa.py``."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    model["rope_theta"] = cfg["rope_parameters"]["hybrid"]["rope_theta"]
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["num_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    for key in SEEDING_KEYS:
        if key in cfg:
            model[key] = cfg[key]
    layers = int(cfg["num_hidden_layers"])
    model["n_routed_experts"] = int(cfg["num_experts"])
    model["first_k_dense_replace"] = 0
    model["layer_types"] = [FULL] * layers
    model["num_attention_heads_per_layer"] = \
        [int(cfg["num_attention_heads"])] * layers
    return model


def model_config(m):
    from paddle_tpu.models.zaya import ZayaConfig

    return ZayaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], cca_time0=m["cca_time0"],
        cca_time1=m["cca_time1"],
        partial_rotary_factor=m["partial_rotary_factor"],
        rope_theta=m["rope_theta"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=m["router_experts"], num_local_experts=m["num_experts"],
        expert_offset=m["expert_offset"],
        num_experts_per_tok=m["num_experts_per_tok"],
        router_hidden_size=m["router_hidden_size"],
        rms_norm_eps=m["rms_norm_eps"],
        initializer_range=m["initializer_range"],
        tie_word_embeddings=m["tie_word_embeddings"])


def build_model(ctx):
    """The program's model in the stated precision, holding the SEEDED
    weights of the reference module."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.zaya import ZayaForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = ZayaForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    ref_mod = ctx.reference()
    with_bias.load_seeded(model, ref_mod.init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), ref_mod, m)
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(by_group.Program):
    """``laguna_train.Program`` (the counters kept a call, the state read by
    the reference's groups, the parameters kept after the first
    ``check_steps`` steps) over this family's model."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda logits, labels: self.model.loss(logits,
                                                               labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._group_of = functools.partial(ctx.reference().group_of,
                                           ctx.config["model"])
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept


def state_rms_gap(got, want):
    """The largest relative gap between the program's and the reference's
    router-state RMS, over the first steps and the layers past the first
    (zeros enter the first on both sides)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)[:, 1:]
                        / np.maximum(want[:, 1:], 1e-30)))


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = {"counters": [], "params": None}
    ref = moe._KeepingResults(ctx.reference(), kept)
    ctx.reference = lambda: ref
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))

    want = ref.results["float32"]
    controls = {p: out for p, out in ref.results.items() if p != "float32"}
    moe.check_direction(ctx, "param_change_direction_gap", want)
    for precision, out in controls.items():
        moe.check_direction(
            ctx, f"control.{precision}.param_change_direction_gap", out)

    k = int(cfg["check_steps"])
    counts = [np.asarray(c[moe.COUNTER]).tolist() for c in kept["counters"]]
    rms = [np.asarray(c[STATE_RMS]).tolist() for c in kept["counters"]]
    ctx.check("expert_count_gap",
              moe.count_gap(counts[:k], want["expert_counts"]),
              ctx.limit("expert_count_gap"),
              detail=f"first step, layer by layer: program "
                     f"{np.sum(counts[0], axis=1).tolist()}, reference "
                     f"{np.sum(want['expert_counts'][0], axis=1).tolist()} "
                     f"tokens")
    ctx.check("router_state_rms_gap",
              state_rms_gap(rms[:k], want[STATE_RMS]),
              ctx.limit("router_state_rms_gap"),
              detail=f"first step, entering each layer: program "
                     f"{np.round(rms[0], 4).tolist()}, reference "
                     f"{np.round(want[STATE_RMS][0], 4).tolist()}")
    for precision, out in controls.items():
        ctx.check(f"control.{precision}.expert_count_gap",
                  moe.count_gap(out["expert_counts"], want["expert_counts"]),
                  ctx.limit("expert_count_gap"))
        ctx.check(f"control.{precision}.router_state_rms_gap",
                  state_rms_gap(out[STATE_RMS], want[STATE_RMS]),
                  ctx.limit("router_state_rms_gap"))

    # calls: k first steps, one uncounted, then the window's records
    first = k + 1
    window = counts[first:first + len(res["steps"])]
    res["counters"] = {moe.COUNTER: window,
                       STATE_RMS: rms[first:first + len(res["steps"])]}
    if window:
        tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
        ctx.note(f"counters: {moe.COUNTER} over {len(window)} window steps: "
                 f"tokens served here a step, of {tokens} routed in each of "
                 f"{len(window[0])} expert layers (top-1, none dropped): "
                 f"{[int(np.sum(c)) for c in window]}; by layer in the "
                 f"first and the last step "
                 f"{np.sum(window[0], axis=1).tolist()} -> "
                 f"{np.sum(window[-1], axis=1).tolist()}")
        ctx.note(f"counters: {STATE_RMS} entering each layer, first and "
                 f"last step of the window: "
                 f"{np.round(res['counters'][STATE_RMS][0], 4).tolist()} -> "
                 f"{np.round(res['counters'][STATE_RMS][-1], 4).tolist()}")
    return res
