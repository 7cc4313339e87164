"""Runner ``mla_moe_train``: a latent-attention decoder with routed and
shared experts (``paddle_tpu.models.mla_moe``) through ``amp.decorate`` O2
-> ``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program
and what is done with its counters is ``runners/train.py``'s ``run``.

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run; ``deployment`` says what the chip holds of a layer
(``router_experts``: the router's published width, ``expert_offset``: the
first expert held) and ``trainer`` how the step is built (``remat``:
``TrainStep``'s, each decoder layer rematerialised in the backward pass).  :func:`model_group`
gathers them into the ``model`` group ``train.run`` and the reference take.

The step hands back, beside the loss, the tokens each expert held here
received in each expert layer (``TrainStep.counters``).  The runner keeps
them as device arrays, reads them after the window, gives the window's to
the readers as ``res["counters"]`` (``env.res`` there) and compares the
first steps' with the reference's own (``expert_count_gap``).

It also keeps, on the host, the parameters the first ``check_steps`` steps
ended in, and has the reference say which WAY each leaf moved beside its
own change (``param_change_direction_gap``): the norms ``train.run``
compares are blind to an update of the right size in the wrong direction.
"""

import functools

import numpy as np

from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_hidden_layers",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "q_lora_rank", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "first_k_dense_replace", "routed_scaling_factor", "norm_topk_prob",
    "rms_norm_eps", "rope_theta", "rope_interleave", "vocab_size",
    "max_position_embeddings")


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, and the sizes the benchmark assumes."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["n_routed_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    return model


def model_config(m):
    from paddle_tpu.models.mla_moe import MlaMoeConfig

    return MlaMoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"], q_lora_rank=m["q_lora_rank"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_routed_experts=m["router_experts"],
        num_local_experts=m["n_routed_experts"],
        expert_offset=m["expert_offset"],
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        first_k_dense_replace=m["first_k_dense_replace"],
        routed_scaling_factor=m["routed_scaling_factor"],
        norm_topk_prob=m["norm_topk_prob"], rms_norm_eps=m["rms_norm_eps"],
        rope_theta=m["rope_theta"], rope_interleave=m["rope_interleave"],
        max_position_embeddings=m["max_position_embeddings"],
        initializer_range=m["initializer_range"])


def program_key(name, first_k):
    """``model.layers.3.moe.router.weight`` -> ("moe",
    "moe.router.weight", 3): the group says the kind of layer."""
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        layer = int(parts[2])
        return ("dense" if layer < first_k else "moe",
                ".".join(parts[3:]), layer)
    if parts[:2] == ["model", "embeddings"]:
        return "embed", ".".join(parts[2:]), None
    if parts[0] == "lm_head" or parts[:2] == ["model", "ln_f"]:
        return "head", ".".join(parts[-2:]), None
    raise KeyError(name)


def load_seeded(model, tree, first_k):
    """The reference's seeded tree into the program's model: a layer's
    leaves are rows of the reference's stacks; the selection bias is a zero
    buffer on both sides and is not loaded."""
    from paddle_tpu.core.tensor import Tensor

    bias = "e_score_correction_bias"
    sd = {}
    for name, t in model.state_dict().items():
        if name.endswith(bias):
            continue
        group, leaf, layer = program_key(name, first_k)
        a = tree[group][leaf]
        if layer is not None:
            a = a[layer if group == "dense" else layer - first_k]
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
    from paddle_tpu.models.mla_moe import MlaMoeForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = MlaMoeForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    load_seeded(model, ctx.reference().init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), m["first_k_dense_replace"])
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(train.Program):
    """``train.Program`` with this family's model and names.  Every call
    leaves the step's counters, unread, in ``kept["counters"]``; the
    parameters read after the first ``check_steps`` steps stay, on the
    host, in ``kept["params"]``."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda logits, labels: self.model.loss(logits,
                                                               labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._first_k = ctx.config["model"]["first_k_dense_replace"]
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept

    def __call__(self, ids, labels):
        loss = self.step(ids, labels)
        self._kept["counters"].append(self.step.counters)
        return loss

    def state(self):
        sd = self.step.state_dict()
        params, moments = {}, {}
        for name, a in sd["params"].items():
            group, leaf, layer = program_key(name, self._first_k)
            params[(f"{group}.{leaf}", layer)] = a
            moments[(f"{group}.{leaf}", layer)] = \
                sd["opt_state"][name]["moment1"]
        if len(self._kept["counters"]) == self._check_steps:
            import jax
            self._kept["params"] = jax.device_get(params)
        return params, moments


class _KeepingResults:
    """The reference module, remembering what ``train_reference`` gave
    (``train.run`` compares losses and norms; the counts and the
    directions are compared here).  The float32 reference is asked which
    way the PROGRAM's parameters moved beside its own, a control which way
    it moved itself beside the float32 reference."""

    def __init__(self, module, kept):
        self._module, self._kept, self.results = module, kept, {}

    def __getattr__(self, name):
        return getattr(self._module, name)

    def train_reference(self, *args, precision="float32", **kwargs):
        against = self._kept["params"] if precision == "float32" \
            else self.results["float32"]["params"]
        out = self._module.train_reference(*args, precision=precision,
                                           against=against, **kwargs)
        self.results[precision] = out
        return out


COUNTER = "moe_tokens_per_expert"


def count_gap(got, want):
    """Share of the assignments the reference serves here that the two
    sides count under different experts: half the summed difference of
    the per-expert counts over the reference's total, worst step.  A
    lower bound of the assignments that differ (two that swap experts
    cancel)."""
    worst = 0.0
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.int64), np.asarray(w, np.int64)
        worst = max(worst, 0.5 * float(np.abs(g - w).sum())
                    / max(float(w.sum()), 1.0))
    return worst


def direction_gap(cosines):
    """(1 - the smallest cosine, the three worst leaves): 0 where every
    leaf moved the reference's way, 2 where one moved straight against
    it."""
    worst = sorted((c, k) for k, c in cosines.items())
    return 1.0 - worst[0][0], [(k, round(c, 4)) for c, k in worst[:3]]


def check_direction(ctx, name, out):
    gap, worst = direction_gap(out["param_change_cosines"])
    ctx.check(name, gap, ctx.limit("param_change_direction_gap"),
              detail=f"cosine of the parameters' change with the "
                     f"reference's, smallest {worst}; over all leaves "
                     f"{out['param_change_cosine_all']:.4f}")


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = {"counters": [], "params": None}
    ref = _KeepingResults(ctx.reference(), kept)
    ctx.reference = lambda: ref
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))

    check_direction(ctx, "param_change_direction_gap",
                    ref.results["float32"])
    for precision, out in ref.results.items():
        if precision != "float32":
            check_direction(
                ctx, f"control.{precision}.param_change_direction_gap", out)

    k = int(cfg["check_steps"])
    counts = [np.asarray(c[COUNTER]).tolist() for c in kept["counters"]
              if COUNTER in c]
    if counts:
        # calls: k first steps, one uncounted, then the window's records
        window = counts[k + 1:k + 1 + len(res["steps"])]
        res["counters"] = {COUNTER: window}
        tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
        served = [int(np.sum(c)) for c in window]
        n_moe = len(window[0]) if window else 0
        ctx.note(f"counters: {COUNTER} over {len(window)} window steps: "
                 f"assignments served here a step, of "
                 f"{tokens * cfg['model']['num_experts_per_tok']} made in "
                 f"each of {n_moe} expert layers (none dropped): {served}")
        want = ref.results["float32"]["expert_counts"]
        ctx.check("expert_count_gap", count_gap(counts[:k], want),
                  ctx.limit("expert_count_gap"),
                  detail=f"first step, layer by layer: program "
                         f"{np.sum(counts[0], axis=1).tolist()}, reference "
                         f"{np.sum(want[0], axis=1).tolist()} assignments")
        for precision, out in ref.results.items():
            if precision != "float32":
                ctx.check(f"control.{precision}.expert_count_gap",
                          count_gap(out["expert_counts"], want),
                          ctx.limit("expert_count_gap"))
    return res
