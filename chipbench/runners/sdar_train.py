"""Runner ``sdar_train``: an expert decoder trained by block diffusion
(``paddle_tpu.models.sdar``: the noised and the clean copy of a row through
one stack under the block-diffusion mask), through ``amp.decorate`` O2 ->
``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program is
``runners/train.py``'s ``run``; the program's state is read and the seeded
weights are loaded as ``runners/laguna_train.py`` does it, and what is done
with the experts' counter and with the direction of the parameters' change is
``runners/mla_moe_train.py``'s (``README.sdar.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run, the objective's ``block_length`` and
``mask_token_id`` beside them; ``deployment`` says what the chip holds of a
layer and ``trainer`` how the step is built.  :func:`model_group` gathers
them into the ``model`` group ``train.run``, the reference and the readers
take.

The feed (``traffic_kinds/block_diffusion.py``) returns ``(ids, (noised ids,
noise))``; ``train.run`` hands both to :class:`Program`, which opens the
second: the step's inputs are ``(ids, noised)``, its labels ``(ids, noised,
noise)``.  Beside the loss the step hands back the experts' counters,
``blockdiff_masked_tokens`` (its loss terms) and ``blockdiff_pairs_scored`` /
``_needed``; the first steps' masked count is held to the reference's own,
exactly (``masked_loss_terms_gap``).
"""

import functools

import numpy as np

from . import laguna_train as grouped
from . import mla_moe_train as moe
from . import train

# the source's keys that shape the model (architectures.jsonl `config`), and
# the objective's two, which the source's config has no key for
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "rope_theta", "rms_norm_eps", "vocab_size", "block_length",
    "mask_token_id")
COUNTERS = (moe.COUNTER, "blockdiff_masked_tokens", "blockdiff_pairs_scored",
            "blockdiff_pairs_needed")


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, and under the names the accepted cost functions read
    (``kernel_costs/moe_grouped_matmul.py``): ``n_routed_experts``, the
    experts held, and ``first_k_dense_replace``, the dense layers (none)."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["num_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    # how the SEEDED weights are drawn (the reference module's to read)
    for key in ("embedding_range", "mask_row", "qk_norm_gain"):
        if key in cfg:
            model[key] = cfg[key]
    model["n_routed_experts"] = int(cfg["num_experts"])
    model["first_k_dense_replace"] = 0
    return model


def model_config(m):
    from paddle_tpu.models.sdar import SdarConfig

    return SdarConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=m["router_experts"], num_local_experts=m["num_experts"],
        expert_offset=m["expert_offset"],
        num_experts_per_tok=m["num_experts_per_tok"],
        norm_topk_prob=m["norm_topk_prob"],
        decoder_sparse_step=m["decoder_sparse_step"],
        mlp_only_layers=m["mlp_only_layers"], rope_theta=m["rope_theta"],
        rms_norm_eps=m["rms_norm_eps"],
        initializer_range=m["initializer_range"],
        block_length=m["block_length"], mask_token_id=m["mask_token_id"])


def build_model(ctx):
    """The program's model in the stated precision, holding the SEEDED
    weights of the reference module."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.sdar import SdarForBlockDiffusion

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = SdarForBlockDiffusion(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    ref_mod = ctx.reference()
    grouped.load_seeded(model, ref_mod.init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), ref_mod, m)
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(grouped.Program):
    """``laguna_train.Program`` (the counters kept a call, the state read by
    the reference's groups, the parameters kept after the first
    ``check_steps`` steps) over this family's model, whose step takes the
    clean and the noised ids and whose loss takes the noise besides."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda logits, *labels: self.model.loss(logits,
                                                                *labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._group_of = functools.partial(ctx.reference().group_of,
                                           ctx.config["model"])
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept

    def put(self, x):
        """The ids, or the feed's ``(noised ids, noise)``."""
        if isinstance(x, tuple):
            return tuple(self.put(a) for a in x)
        return super().put(x)

    def __call__(self, ids, labels):
        noised, noise = labels
        loss = self.step((ids, noised), (ids, noised, noise))
        self._kept["counters"].append(self.step.counters)
        return loss


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    feed_mask = ctx.traffic_kind().generate(
        ctx.traffic, ctx.seed, ctx.seconds,
        cfg["model"]["vocab_size"]).mask_token_id
    if (feed_mask != cfg["model"]["mask_token_id"]
            or ctx.traffic["block"] != cfg["model"]["block_length"]):
        raise ValueError("traffic and configuration disagree on the mask "
                         "token or the block length")
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
    counts = {name: [np.asarray(c[name]).tolist() for c in kept["counters"]]
              for name in COUNTERS}
    experts, masked = counts[moe.COUNTER], counts["blockdiff_masked_tokens"]
    ctx.check("expert_count_gap",
              moe.count_gap(experts[:k], want["expert_counts"]),
              ctx.limit("expert_count_gap"),
              detail=f"first step, layer by layer: program "
                     f"{np.sum(experts[0], axis=1).tolist()}, reference "
                     f"{np.sum(want['expert_counts'][0], axis=1).tolist()} "
                     f"assignments")
    terms_gap = max(abs(g - w) for g, w in zip(masked[:k],
                                               want["masked_tokens"]))
    ctx.check("masked_loss_terms_gap", terms_gap,
              ctx.limit("masked_loss_terms_gap"),
              detail=f"loss terms of the first steps: program {masked[:k]}, "
                     f"reference {want['masked_tokens']}")
    for precision, out in controls.items():
        ctx.check(f"control.{precision}.expert_count_gap",
                  moe.count_gap(out["expert_counts"], want["expert_counts"]),
                  ctx.limit("expert_count_gap"))

    # calls: k first steps, one uncounted, then the window's records
    first = k + 1
    res["counters"] = {name: steps[first:first + len(res["steps"])]
                       for name, steps in counts.items()}
    window = res["counters"]
    positions = 2 * ctx.traffic["batch"] * ctx.traffic["seq"]
    if window[moe.COUNTER]:
        ctx.note(f"counters: {moe.COUNTER} over {len(window[moe.COUNTER])} "
                 f"window steps: assignments served here a step, of "
                 f"{positions * cfg['model']['num_experts_per_tok']} made in "
                 f"each of {len(window[moe.COUNTER][0])} expert layers (none "
                 f"dropped): {[int(np.sum(c)) for c in window[moe.COUNTER]]}")
        ctx.note(f"counters: blockdiff_masked_tokens a step, of "
                 f"{positions // 2} data tokens: "
                 f"{window['blockdiff_masked_tokens']}; pairs a row, head "
                 f"and layer scored {window['blockdiff_pairs_scored'][0]}, "
                 f"needed {window['blockdiff_pairs_needed'][0]}")
    return res
