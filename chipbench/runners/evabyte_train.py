"""Runner ``evabyte_train``: a byte-level decoder whose attention is exact
inside a block-aligned window and reads chunk summaries of the windows
before it, with several next-byte heads (``paddle_tpu.models.evabyte``),
through ``amp.decorate`` O2 -> ``jit.TrainStep`` + ``AdamW`` on one chip.
Everything but the program and what is done with its counters is
``runners/train.py``'s ``run`` (``README.evabyte.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run, and ``trainer`` says how the step is built
(``remat``: ``TrainStep``'s).  :func:`model_group` gathers the keys that
shape the model into the ``model`` group ``train.run``, the reference and the
readers take.

The step hands back, beside the loss, ``eva_pairs_scored`` and
``eva_pairs_needed`` (``TrainStep.counters``; one row and head's pairs,
layer by layer).  The runner keeps them as device arrays, reads them after
the window and gives the window's to the readers as ``res["counters"]``
(``env.res`` there).
"""

import functools

import numpy as np

from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "window_size", "chunk_size",
    "num_pred_heads", "rope_theta", "rms_norm_eps", "init_std", "vocab_size")
COUNTERS = ("eva_pairs_scored", "eva_pairs_needed")


def model_group(cfg):
    return {k: cfg[k] for k in MODEL_KEYS}


def model_config(m):
    from paddle_tpu.models.evabyte import EvaByteConfig

    return EvaByteConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        intermediate_size=m["intermediate_size"],
        window_size=m["window_size"], chunk_size=m["chunk_size"],
        num_pred_heads=m["num_pred_heads"], rope_theta=m["rope_theta"],
        rms_norm_eps=m["rms_norm_eps"], init_std=m["init_std"])


def program_key(name):
    """``model.layers.3.attn.mu`` -> ("blocks", "attn.mu", 3)."""
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        return "blocks", ".".join(parts[3:]), int(parts[2])
    if parts[:2] == ["model", "embeddings"]:
        return "embed", ".".join(parts[2:]), None
    if parts[0] == "lm_head" or parts[:2] == ["model", "ln_f"]:
        return "head", ".".join(parts[-2:]), None
    raise KeyError(name)


def load_seeded(model, tree):
    """The reference's seeded tree into the program's model: a layer's
    leaves are rows of the reference's stacks."""
    from paddle_tpu.core.tensor import Tensor

    sd = {}
    for name, t in model.state_dict().items():
        group, leaf, layer = program_key(name)
        a = tree[group][leaf]
        if layer is not None:
            a = a[layer]
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
    from paddle_tpu.models.evabyte import EvaByteForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = EvaByteForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    load_seeded(model, ctx.reference().init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])))
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(train.Program):
    """``train.Program`` with this family's model and names.  Every call
    leaves the step's counters, unread, in ``kept``."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda logits, labels: self.model.loss(logits,
                                                               labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._kept = kept

    def __call__(self, ids, labels):
        loss = self.step(ids, labels)
        self._kept.append(self.step.counters)
        return loss

    def state(self):
        sd = self.step.state_dict()
        params, moments = {}, {}
        for name, a in sd["params"].items():
            group, leaf, layer = program_key(name)
            params[(f"{group}.{leaf}", layer)] = a
            moments[(f"{group}.{leaf}", layer)] = \
                sd["opt_state"][name]["moment1"]
        return params, moments


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = []
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))
    # calls: check_steps first steps, one uncounted, then the window's
    first = int(cfg["check_steps"]) + 1
    window = kept[first:first + len(res["steps"])]
    res["counters"] = {name: [np.asarray(c[name]).tolist() for c in window]
                       for name in COUNTERS if window and name in window[0]}
    for name, steps in res["counters"].items():
        ctx.note(f"counters: {name}, one row and head's pairs layer by "
                 f"layer, first window step {steps[0]}; the same in all "
                 f"{len(steps)}: {all(s == steps[0] for s in steps)}")
    return res
