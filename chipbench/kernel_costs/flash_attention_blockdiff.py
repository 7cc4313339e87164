"""Operations and bytes of the flash kernels under the block-diffusion mask
(``flash_blockdiff<B>_attention_*``, ``paddle_tpu/ops/pallas/
attention_kernel.py``), for the calls that RAN in the traced window: what
the mask NEEDS and no more.

The row is ``[noised ; clean]``, ``2 L`` positions of ``L`` data tokens in
blocks of ``B``.  A noised query of block ``b`` sees its own block among the
noised keys and the ``b`` earlier blocks among the clean keys, ``B + b B``
keys; a clean query of block ``b`` the clean keys of its own block and the
earlier ones, the same count: ``pairs = rows * L (L + B)`` (67.1M a head at
``L`` 8192, ``B`` 4; the dense square is 268M).  One matmul over them with
inner or outer width ``d`` is ``2 * N * pairs * d`` FLOP (N q heads).  A
forward call runs two (Q K^T, P V), a backward call five (S again, dV, dP,
dQ, dK); the masked part of the blocks the staircase crosses and of the
noised diagonal blocks counts for nothing.  Softmax's exponentials are left
out.

Bytes are the tensors that must cross HBM once a call, over all ``2 L``
positions: K, V, dK and dV once a KV head, not once a q head; q, o, dO and
dq once a q head; the row statistics once a q head (float32; lse forward,
lse and delta backward).

The calls are COUNTED in the trace, as ``flash_attention_gqa`` counts its
own (``flash_blockdiff..._fwd`` events are forward calls, ``..._bwd_dq``
events backward calls): with the layers rematerialised and the flash
forward's results kept, each runs once a layer and step.
"""

from .flash_attention_gqa import calls_in_window

MARK = "flash_blockdiff"


def pairs(seq, block):
    """The (query, key) pairs one row and head holds: ``seq`` data tokens."""
    return seq * (seq + block)


def call_costs(batch, seq, block, q_heads, kv_heads, head_dim, itemsize=2):
    """((forward FLOP, bytes), (backward FLOP, bytes)) of one call over
    ``batch`` rows of ``seq`` DATA tokens (``2 * seq`` positions)."""
    unit = 2.0 * batch * q_heads * pairs(seq, block) * head_dim
    per_q = batch * 2 * seq * q_heads * head_dim * itemsize
    per_kv = batch * 2 * seq * kv_heads * head_dim * itemsize
    stats = batch * q_heads * 2 * seq * 4
    fwd = (2 * unit, 2 * per_q + 2 * per_kv + stats)
    bwd = (5 * unit, (3 * per_q + 2 * per_kv + 2 * stats)
           + (per_q + 2 * per_kv))
    return fwd, bwd


def window_cost(env):
    model = env.config["model"]
    (f_flop, f_bytes), (b_flop, b_bytes) = call_costs(
        env.traffic["batch"], env.traffic["seq"], int(model["block_length"]),
        int(model["num_attention_heads"]), int(model["num_key_value_heads"]),
        int(model["head_dim"]))
    n_fwd, n_bwd = calls_in_window(env, MARK)
    env.ctx.note(f"{MARK}: {n_fwd} forward and {n_bwd} backward calls in "
                 f"the window, {len(env.steps)} steps of "
                 f"{model['num_hidden_layers']} layers")
    return (n_fwd * f_flop + n_bwd * b_flop,
            n_fwd * f_bytes + n_bwd * b_bytes)
