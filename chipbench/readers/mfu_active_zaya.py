"""Model FLOP/s utilisation of training the compressed-attention expert
family (``model_type`` ``zaya``), in %, as ``readers/mfu_active.py`` reckons
it for the latent-attention family, a share of the WHOLE step:

    6 * N_active * tokens/s/chip / bf16 peak of the attached device_kind

``N_active`` is what a token really multiplies HERE: a layer's four
projections (q and o ``H x n D``, k and v ``H x kv D``: attention's latent
is narrower than ``H``), the depthwise taps and the per-head taps of the
two convolutions (``D x D`` a head and tap), the router (``H x S`` down, two
``S x S`` and ``S x E``) and, of the routed experts, the ONE that served
the token if it is held on this chip: an expert's ``3 H I`` times the share
of tokens served here, from the program's own counter over the window's
steps (``res["counters"]``), not from the routing's expectation; and the
head, which is the embedding (tied: a lookup on the way in, a matmul on the
way out).  Attention's own operations (the causal pairs) are LEFT OUT, as
``mfu_pct`` leaves them out, so are norms, scales and the elementwise chain
between the projections and the flash call, and recomputation counts for
nothing.  Returns nothing where the runner handed over no counter or the
model is not of this family.
"""


def active_params(model, served_share):
    """What one token multiplies; ``served_share`` of the tokens find their
    expert here."""
    h, d = model["hidden_size"], model["head_dim"]
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    s, e = model["router_hidden_size"], model["router_experts"]
    attn = (2 * h * n * d + 2 * h * kv * d
            + (n + kv) * d * model["cca_time0"]
            + (n + kv) * model["cca_time1"] * d * d)
    router = h * s + 2 * s * s + s * e
    expert = 3 * h * model["moe_intermediate_size"]
    return (model["num_hidden_layers"] * (attn + router
                                          + served_share * expert)
            + h * model["vocab_size"])


def read(env, moves):
    from .expert_load import served, window_counts

    counts = window_counts(env)
    model = env.config["model"]
    if not counts or not env.steps or "cca_time0" not in model:
        return None
    tokens = env.traffic["batch"] * env.traffic["seq"]
    share = served(counts) / (len(counts) * model["num_hidden_layers"]
                              * tokens)
    n_active = active_params(model, share)
    env.ctx.note(f"mfu_active.zaya: {share:.4f} of the tokens of an expert "
                 f"layer served here, {n_active / 1e6:.1f}M parameters "
                 f"active a token")
    return 100.0 * 6.0 * n_active * env.end_to_end[moves] \
        / env.peaks["bf16_flops"]
