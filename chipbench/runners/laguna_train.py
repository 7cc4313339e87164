"""Runner ``laguna_train``: a decoder whose layers mix window and full
attention over grouped KV heads, with per-head gates and softmax-routed
experts (``paddle_tpu.models.laguna``), through ``amp.decorate`` O2 ->
``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program is
``runners/train.py``'s ``run``; what is done with the step's counters and
with the direction of the parameters' change is ``runners/mla_moe_train
.py``'s, imported from there (``README.laguna.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run; ``deployment`` says what the chip holds of a layer
(``router_experts``: the router's published width, ``expert_offset``: the
first expert held) and ``trainer`` how the step is built (``remat``:
``TrainStep``'s).  :func:`model_group` gathers them into the ``model`` group
``train.run``, the reference and the readers take.
"""

import functools

import numpy as np

from . import mla_moe_train as moe
from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_key_value_heads", "head_dim",
    "num_attention_heads_per_layer", "layer_types", "mlp_layer_types",
    "sliding_window", "rope_parameters", "intermediate_size",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "num_experts", "num_experts_per_tok", "norm_topk_prob",
    "moe_routed_scaling_factor", "rms_norm_eps", "vocab_size")


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, the sizes the benchmark assumes, and under the names the accepted
    cost functions read (``kernel_costs/moe_grouped_matmul.py``):
    ``n_routed_experts``, the experts held, and ``first_k_dense_replace``,
    the dense layers, which lead."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["num_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    kinds = list(cfg["mlp_layer_types"])
    dense = kinds.count("dense")
    if kinds[:dense] != ["dense"] * dense:
        raise ValueError("the dense layers lead in this family")
    model["n_routed_experts"] = int(cfg["num_experts"])
    model["first_k_dense_replace"] = dense
    return model


def model_config(m):
    from paddle_tpu.models.laguna import LagunaConfig

    return LagunaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        num_attention_heads_per_layer=m["num_attention_heads_per_layer"],
        layer_types=m["layer_types"], mlp_layer_types=m["mlp_layer_types"],
        sliding_window=m["sliding_window"],
        rope_parameters=m["rope_parameters"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["shared_expert_intermediate_size"],
        num_experts=m["router_experts"], num_local_experts=m["num_experts"],
        expert_offset=m["expert_offset"],
        num_experts_per_tok=m["num_experts_per_tok"],
        norm_topk_prob=m["norm_topk_prob"],
        moe_routed_scaling_factor=m["moe_routed_scaling_factor"],
        rms_norm_eps=m["rms_norm_eps"],
        initializer_range=m["initializer_range"])


def program_key(name, group_of_layer):
    """``model.layers.3.moe.router.weight`` -> ("window_moe",
    "moe.router.weight", 3): the group says what the layer is made of."""
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        layer = int(parts[2])
        return group_of_layer(layer), ".".join(parts[3:]), layer
    if parts[:2] == ["model", "embeddings"]:
        return "embed", ".".join(parts[2:]), None
    if parts[0] == "lm_head" or parts[:2] == ["model", "ln_f"]:
        return "head", ".".join(parts[-2:]), None
    raise KeyError(name)


def load_seeded(model, tree, ref_mod, m):
    """The reference's seeded tree into the program's model: a layer's
    leaves are rows of its group's stacks."""
    from paddle_tpu.core.tensor import Tensor

    ids = ref_mod.layer_ids(m)
    sd = {}
    for name, t in model.state_dict().items():
        group, leaf, layer = program_key(
            name, functools.partial(ref_mod.group_of, m))
        a = tree[group][leaf]
        if layer is not None:
            a = a[ids[group].index(layer)]
        if a.shape != tuple(t.shape):
            raise RuntimeError(f"{name}: {a.shape} for {tuple(t.shape)}")
        sd[name] = Tensor(a)
    missing, unexpected = model.set_state_dict(sd)
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit: {missing} {unexpected}")


def build_model(ctx):
    """The program's model in the stated precision, holding the SEEDED
    weights of the reference module."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.laguna import LagunaForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = LagunaForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    ref_mod = ctx.reference()
    load_seeded(model, ref_mod.init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), ref_mod, m)
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(moe.Program):
    """``mla_moe_train.Program`` (the counters kept a call, the parameters
    kept after the first ``check_steps`` steps) over this family's model
    and names."""

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

    def state(self):
        sd = self.step.state_dict()
        params, moments = {}, {}
        for name, a in sd["params"].items():
            group, leaf, layer = program_key(name, self._group_of)
            params[(f"{group}.{leaf}", layer)] = a
            moments[(f"{group}.{leaf}", layer)] = \
                sd["opt_state"][name]["moment1"]
        if len(self._kept["counters"]) == self._check_steps:
            import jax
            self._kept["params"] = jax.device_get(params)
        return params, moments


def run(ctx):
    """``mla_moe_train.run`` with this family's model group and program
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
