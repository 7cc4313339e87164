"""Runner ``ouro_train``: a decoder whose whole layer stack runs
``total_ut_steps`` times over the same parameters, an exit gate weighing
each pass's loss (``paddle_tpu.models.ouro``), through ``amp.decorate`` O2 ->
``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program and
what is done with its counters is ``runners/train.py``'s ``run``; the
direction of the parameters' change is held as ``runners/mla_moe_train.py``
holds it (``README.ouro.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run, the objective's ``exit_entropy_beta`` beside them,
and ``trainer`` says how the step is built (``remat``: ``TrainStep``'s).
:func:`model_group` gathers the keys that shape the model into the ``model``
group ``train.run``, the reference and the readers take.

The step takes ``(ids, labels)`` as the model's inputs (head and cross
entropy run inside each pass) and hands back, beside the loss,
``ouro_pass_loss`` and ``ouro_exit_mass`` (``TrainStep.counters``, float32
``[total_ut_steps]``).  The runner keeps them as device arrays, reads them
after the window, gives the window's to the readers as ``res["counters"]``
(``env.res`` there) and compares the first steps' with the reference's own
(``pass_loss_gap``, ``exit_mass_gap``).
"""

import functools

import numpy as np

from . import mla_moe_train as moe
from . import train

# the source's keys that shape the model (architectures.jsonl `config`), and
# the objective's weight, which the source's config has no key for
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "total_ut_steps",
    "early_exit_threshold", "rope_theta", "rms_norm_eps", "vocab_size",
    "exit_entropy_beta")
COUNTERS = ("ouro_pass_loss", "ouro_exit_mass")


def model_group(cfg):
    model = {k: cfg[k] for k in MODEL_KEYS}
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    return model


def model_config(m):
    """The model group's keys are ``OuroConfig``'s own."""
    from paddle_tpu.models.ouro import OuroConfig

    return OuroConfig(**m)


def program_key(name):
    """``model.layers.3.ln_1b.weight`` -> ("blocks", "ln_1b.weight", 3);
    ``exit_gate.bias`` -> ("head", "exit_gate.bias", None)."""
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        return "blocks", ".".join(parts[3:]), int(parts[2])
    if parts[:2] == ["model", "embeddings"]:
        return "embed", ".".join(parts[2:]), None
    if parts[0] in ("lm_head", "exit_gate") or parts[:2] == ["model", "ln_f"]:
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
    from paddle_tpu.models.ouro import OuroForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = OuroForCausalLM(model_config(m))
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
    leaves the step's counters, unread, in ``kept["counters"]``; the
    parameters the first ``check_steps`` steps ended in are kept on the
    host for the direction check."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda out, labels: self.model.loss(out, labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept

    def __call__(self, ids, labels):
        loss = self.step((ids, labels), labels)
        self._kept["counters"].append(self.step.counters)
        return loss

    def state(self):
        sd = self.step.state_dict()
        params, moments = {}, {}
        for name, a in sd["params"].items():
            group, leaf, layer = program_key(name)
            params[(f"{group}.{leaf}", layer)] = a
            moments[(f"{group}.{leaf}", layer)] = \
                sd["opt_state"][name]["moment1"]
        if len(self._kept["counters"]) == self._check_steps:
            import jax
            self._kept["params"] = jax.device_get(params)
        return params, moments


def worst_gap(got, want):
    """The largest ``|got - want|`` over steps and passes."""
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64))))


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = {"counters": [], "params": None}
    ref = moe._KeepingResults(ctx.reference(), kept)
    ctx.reference = lambda: ref
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))

    want = ref.results["float32"]
    moe.check_direction(ctx, "param_change_direction_gap", want)
    k = int(cfg["check_steps"])
    counts = {name: [np.asarray(c[name]).tolist() for c in kept["counters"]]
              for name in COUNTERS}
    checks = (("pass_loss_gap", "ouro_pass_loss", "pass_losses"),
              ("exit_mass_gap", "ouro_exit_mass", "exit_masses"))
    for check, counter, key in checks:
        ctx.check(check, worst_gap(counts[counter][:k], want[key]),
                  ctx.limit(check),
                  detail=f"first step, pass by pass: program "
                         f"{np.round(counts[counter][0], 5).tolist()}, "
                         f"reference {np.round(want[key][0], 5).tolist()}")
    for precision, out in ref.results.items():
        if precision == "float32":
            continue
        moe.check_direction(
            ctx, f"control.{precision}.param_change_direction_gap", out)
        for check, _, key in checks:
            ctx.check(f"control.{precision}.{check}",
                      worst_gap(out[key], want[key]), ctx.limit(check))

    # calls: k first steps, one uncounted, then the window's records
    first = k + 1
    res["counters"] = {name: steps[first:first + len(res["steps"])]
                       for name, steps in counts.items()}
    mass = np.asarray(res["counters"]["ouro_exit_mass"], np.float64)
    if mass.size:
        ctx.note(f"counters: the mean exit pass, sum_r r x ouro_exit_mass[r],"
                 f" step by step: "
                 f"{np.round(mass @ np.arange(1, mass.shape[1] + 1), 3).tolist()}")
        ctx.note(f"counters: ouro_exit_mass over {len(mass)} window steps, "
                 f"first {np.round(mass[0], 5).tolist()}, last "
                 f"{np.round(mass[-1], 5).tolist()}; its sum is off 1 by at "
                 f"most {float(np.max(np.abs(mass.sum(axis=1) - 1.0))):.2e};"
                 f" ouro_pass_loss first "
                 f"{np.round(res['counters']['ouro_pass_loss'][0], 4).tolist()}"
                 f", last "
                 f"{np.round(res['counters']['ouro_pass_loss'][-1], 4).tolist()}")
    return res
