"""Model FLOP/s utilisation of training a LOOPED dense decoder, in %:

    6 * R * N_pass * tokens/s/chip / bf16 peak of the attached device_kind

``N_pass`` is what a token multiplies in ONE pass of the stack: every
block's attention projections and gated MLP and the head, which reads the
state after every pass: ``L (2 H n D + 2 H kv D + 3 H I) + H V``; ``R`` =
``total_ut_steps`` passes run over the same weights, so a weight is counted
``R`` times a token though it is held (and stepped by the optimizer) once.
The embedding is a lookup, the norms' gains and the exit gate's 2,049
numbers are left out, attention's own operations (the T^2 terms) are LEFT
OUT, as ``mfu_pct`` leaves them out, and recomputation counts for nothing,
so the share cannot pass 100.
"""


def multiplied_per_token(model):
    h, d = model["hidden_size"], model["head_dim"]
    n = model["num_attention_heads"]
    kv = model.get("num_key_value_heads") or n
    block = 2 * h * n * d + 2 * h * kv * d + 3 * h * model["intermediate_size"]
    return model["total_ut_steps"] * (
        model["num_hidden_layers"] * block + h * model["vocab_size"])


def read(env, moves):
    model = env.config["model"]
    if "total_ut_steps" not in model:
        return None
    n = multiplied_per_token(model)
    env.ctx.note(f"mfu_looped: {n / 1e6:.1f}M multiply-adds a token forward "
                 f"over {model['total_ut_steps']} passes")
    return 100.0 * 6.0 * n * env.end_to_end[moves] / env.peaks["bf16_flops"]
