"""Runner ``nemotron_h_train``: a hybrid decoder whose blocks hold a Mamba-2
mixer, a grouped-KV attention or an expert layer in a latent ALONE
(``paddle_tpu.models.nemotron_h``), through ``amp.decorate`` O2 ->
``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program is
``runners/train.py``'s ``run``; how a layer's leaves are named and read is
``runners/laguna_train.py``'s (a group a kind of layer, by the reference's
``group_of``), what is done with the step's counters and with the direction
of the parameters' change ``runners/mla_moe_train.py``'s, both imported
(``README.nemotron_h.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run; ``deployment`` says what the chip holds of a layer
(``router_experts``: the router's published width, ``expert_offset``: the
first expert held) and ``trainer`` how the step is built (``remat``:
``TrainStep``'s; ``moe_bucket_headroom``: the expert layers' small bucket in
rows expected, ``DroplessMoELayer.bucket_headroom``).  :func:`model_group` gathers them into the ``model`` group
``train.run``, the reference and the readers take.
"""

import functools

import numpy as np

from ..kernel_costs.flash_attention_gqa import FULL
from . import laguna_train as by_group
from . import mla_moe_train as moe
from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "use_conv_bias", "num_attention_heads",
    "num_key_value_heads", "head_dim", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "mlp_hidden_act", "layer_norm_epsilon",
    "time_step_min", "time_step_max", "time_step_floor", "vocab_size")


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, the sizes the benchmark assumes, and, under the names the accepted
    cost function of the grouped flash calls reads
    (``kernel_costs/flash_attention_gqa.py``), ``layer_types`` (the ``*``
    layers are its full-attention layers, every other layer has no
    attention) and ``num_attention_heads_per_layer``."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["n_routed_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    pattern = cfg["hybrid_override_pattern"]
    model["layer_types"] = [FULL if c == "*" else "none" for c in pattern]
    model["num_attention_heads_per_layer"] = [
        int(cfg["num_attention_heads"]) if c == "*" else 0 for c in pattern]
    return model


def model_config(m):
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        hybrid_override_pattern=m["hybrid_override_pattern"],
        mamba_num_heads=m["mamba_num_heads"],
        mamba_head_dim=m["mamba_head_dim"], n_groups=m["n_groups"],
        ssm_state_size=m["ssm_state_size"], conv_kernel=m["conv_kernel"],
        chunk_size=m["chunk_size"], use_conv_bias=m["use_conv_bias"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        n_routed_experts=m["router_experts"],
        num_local_experts=m["n_routed_experts"],
        expert_offset=m["expert_offset"],
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        moe_latent_size=m["moe_latent_size"],
        moe_shared_expert_intermediate_size=m[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=m["routed_scaling_factor"],
        norm_topk_prob=m["norm_topk_prob"],
        mlp_hidden_act=m["mlp_hidden_act"],
        layer_norm_epsilon=m["layer_norm_epsilon"],
        initializer_range=m["initializer_range"],
        time_step_min=m["time_step_min"], time_step_max=m["time_step_max"],
        time_step_floor=m["time_step_floor"])


def load_seeded(model, tree, ref_mod, m):
    """The reference's seeded tree into the program's model: a layer's
    leaves are rows of its group's stacks; the selection bias is a zero
    buffer on both sides and is not loaded."""
    from paddle_tpu.core.tensor import Tensor

    bias = "e_score_correction_bias"
    ids = ref_mod.layer_ids(m)
    sd = {}
    for name, t in model.state_dict().items():
        if name.endswith(bias):
            continue
        group, leaf, layer = by_group.program_key(
            name, functools.partial(ref_mod.group_of, m))
        a = tree[group][leaf]
        if layer is not None:
            a = a[ids[group].index(layer)]
        if a.shape != tuple(t.shape):
            raise RuntimeError(f"{name}: {a.shape} for {tuple(t.shape)}")
        sd[name] = Tensor(a)
    missing, unexpected = model.set_state_dict(sd)
    missing = [n for n in missing if not n.endswith(bias)]
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit: {missing} {unexpected}")


def build_model(ctx):
    """The program's model in the stated precision, holding the SEEDED
    weights of the reference module."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = NemotronHForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    ref_mod = ctx.reference()
    load_seeded(model, ref_mod.init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), ref_mod, m)
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(by_group.Program):
    """``laguna_train.Program`` (the counters kept a call, the parameters
    kept after the first ``check_steps`` steps, a layer's leaves under its
    group) over this family's model."""

    def __init__(self, ctx, kept):
        from paddle_tpu.incubate.distributed.models.moe import \
            DroplessMoELayer
        from paddle_tpu.jit import TrainStep

        trainer = ctx.config.get("trainer", {})
        self.model = build_model(ctx)
        for layer in self.model.sublayers():
            if isinstance(layer, DroplessMoELayer):
                layer.bucket_headroom = trainer.get(
                    "moe_bucket_headroom", layer.bucket_headroom)
        self.step = TrainStep(
            self.model, lambda logits, labels: self.model.loss(logits,
                                                               labels),
            train.optimizer_for(ctx, self.model),
            remat=trainer.get("remat", False))
        self.chips = 1
        self._group_of = functools.partial(ctx.reference().group_of,
                                           ctx.config["model"])
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept


def run(ctx):
    """``laguna_train.run`` with this family's model group and program
    (that function names its own; nothing else differs)."""
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = {"counters": [], "params": None}
    ref = moe._KeepingResults(ctx.reference(), kept)
    ctx.reference = lambda: ref
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))

    moe.check_direction(ctx, "param_change_direction_gap",
                        ref.results["float32"])
    for precision, out in ref.results.items():
        if precision != "float32":
            moe.check_direction(
                ctx, f"control.{precision}.param_change_direction_gap", out)

    k = int(cfg["check_steps"])
    counts = [np.asarray(c[moe.COUNTER]).tolist() for c in kept["counters"]]
    # calls: k first steps, one uncounted, then the window's records
    window = counts[k + 1:k + 1 + len(res["steps"])]
    res["counters"] = {moe.COUNTER: window}
    tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
    ctx.note(f"counters: {moe.COUNTER} over {len(window)} window steps: "
             f"assignments served here a step, of "
             f"{tokens * cfg['model']['num_experts_per_tok']} made in each "
             f"of {len(window[0]) if window else 0} expert layers (none "
             f"dropped): {[int(np.sum(c)) for c in window]}")
    # the bucket each layer's buffers took (``dropless.row_buckets``): the
    # worst case's rows mean the small bucket did not hold a layer's load
    rows, times = np.unique(
        [np.asarray(c["moe_rows_buffered"]).tolist()
         for c in kept["counters"][k + 1:k + 1 + len(window)]],
        return_counts=True)
    ctx.note(f"counters: moe_rows_buffered, layer steps by the rows their "
             f"buffers took: {dict(zip(rows.tolist(), times.tolist()))}; "
             f"the fullest layer of the window held "
             f"{max((sum(l) for c in window for l in c), default=0)} rows")
    want = ref.results["float32"]["expert_counts"]
    ctx.check("expert_count_gap", moe.count_gap(counts[:k], want),
              ctx.limit("expert_count_gap"),
              detail=f"first step, layer by layer: program "
                     f"{np.sum(counts[0], axis=1).tolist()}, reference "
                     f"{np.sum(want[0], axis=1).tolist()} assignments")
    for precision, out in ref.results.items():
        if precision != "float32":
            ctx.check(f"control.{precision}.expert_count_gap",
                      moe.count_gap(out["expert_counts"], want),
                      ctx.limit("expert_count_gap"))
    return res
