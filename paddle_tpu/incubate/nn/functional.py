"""incubate.nn.functional — the fused-op API surface.

Reference: python/paddle/incubate/nn/functional/ (fused_transformer,
fused_matmul_bias, fused_ec_moe, fused_dropout_add...), backed by CUDA
fusion kernels (paddle/fluid/operators/fused/).  On TPU "fused" means
"one traced expression XLA fuses" — these wrappers exist for API parity
and route to the registered fused ops in ops/fused_ops.py, the Pallas
flash-attention kernel, and the MoE dispatch einsums.
"""

import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops.dispatch import apply_op
from ...ops.registry import OPS, register_external

__all__ = ["fused_matmul_bias", "fused_linear", "fused_feedforward",
           "fused_multi_head_attention", "fused_dropout_add",
           "fused_bias_dropout_residual_layer_norm", "fused_ec_moe",
           "fused_rotary_position_embedding", "swiglu"]


def _u(name):
    return OPS[name].user_fn


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """Reference fused_matmul_bias (cublasLt epilogue fusion): matmul with
    the bias add folded in — one XLA fusion here."""
    from ... import matmul

    out = matmul(x, y, transpose_x=transpose_x, transpose_y=transpose_y)
    if bias is not None:
        out = out + bias
    return out


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    return fused_matmul_bias(x, weight, bias,
                             transpose_y=transpose_weight)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode=None,
                      name=None):
    """Reference fused_feedforward (fused_feedforward_op.cu)."""
    return _u("fused_feedforward")(
        x, linear1_weight, linear1_bias, linear2_weight, linear2_bias,
        ln1_scale=ln1_scale, ln1_bias=ln1_bias, ln2_scale=ln2_scale,
        ln2_bias=ln2_bias, dropout1_rate=dropout1_rate,
        dropout2_rate=dropout2_rate, act_method=activation,
        pre_layer_norm=pre_layer_norm, epsilon1=ln1_epsilon,
        epsilon2=ln2_epsilon, is_test=not training)


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode=None, ring_id=-1,
                               add_residual=True, num_heads=None,
                               transpose_qkv_wb=False, name=None):
    """Reference fused_multi_head_attention (fused_attention_op.cu)."""
    return _u("fused_attention")(
        x, qkv_weight, qkv_bias, linear_weight, linear_bias,
        ln_scale=pre_ln_scale if pre_layer_norm else None,
        ln_bias=pre_ln_bias if pre_layer_norm else None,
        ln2_scale=ln_scale,
        ln2_bias=ln_bias,
        num_heads=num_heads, pre_layer_norm=pre_layer_norm,
        epsilon=pre_ln_epsilon, epsilon2=ln_epsilon,
        attn_dropout_rate=attn_dropout_rate,
        dropout_rate=dropout_rate, attn_mask=attn_mask,
        is_test=not training)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """Reference fused_dropout_add: dropout(x) + y in one fusion."""
    return _u("fused_dropout_add")(x, y, p=p, is_test=not training,
                                   mode=mode)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True, mode=None,
        name=None):
    """Reference fused_bias_dropout_residual_layer_norm."""
    h = x if bias is None else x + bias
    h = fused_dropout_add(h, residual, p=dropout_rate, training=training)

    def pure(data, scale, shift):
        mu = data.mean(-1, keepdims=True)
        var = ((data - mu) ** 2).mean(-1, keepdims=True)
        out = (data - mu) / jnp.sqrt(var + ln_epsilon)
        if scale is not None:
            out = out * scale
        if shift is not None:
            out = out + shift
        return out

    return apply_op("fused_bias_dropout_residual_ln", pure,
                    (h, ln_scale, ln_bias), {})


def fused_ec_moe(x, gate_weight, gate_bias, expert_w1, expert_b1, expert_w2,
                 expert_b2, act_type="gelu", name=None):
    """Reference fused_ec_moe (expert-choice MoE one-op path): softmax
    gate → per-expert two-layer FFN → gate-weighted sum.  Dense einsum
    formulation — the same dispatch the MoELayer uses, collapsed to one
    call (GSPMD shards the expert axis when params carry 'ep')."""
    import jax

    def pure(xx, gw, gb, w1, b1, w2, b2):
        gates = jax.nn.softmax(
            jnp.einsum("bsh,he->bse", xx, gw) + gb, -1)
        h = jnp.einsum("bsh,ehm->besm", xx, w1) + b1[None, :, None, :]
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[act_type]
        h = act(h)
        h = jnp.einsum("besm,emh->besh", h, w2) + b2[None, :, None, :]
        return jnp.einsum("besh,bse->bsh", h, gates)

    return apply_op("fused_ec_moe", pure,
                    (x, gate_weight, gate_bias, expert_w1, expert_b1,
                     expert_w2, expert_b2), {})


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    name=None):
    """RoPE applied to q/k (reference incubate fused_rope): interleaved
    (GPT-NeoX) or half-split style.  Differentiable (dispatched op)."""

    def d(t):
        return t._data if isinstance(t, Tensor) else jnp.asarray(t)

    def rope_pure(tt):
        b, s, n, hd = tt.shape
        if position_ids is not None:
            pos = d(position_ids).reshape(b, s).astype(jnp.float32)
        else:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.float32),
                                   (b, s))
        if sin is None or cos is None:
            inv = 1.0 / (10000 ** (jnp.arange(0, hd, 2) / hd))
            ang = pos[..., None] * inv[None, None, :]   # [B, S, D/2]
            sn, cs = jnp.sin(ang), jnp.cos(ang)
        else:
            # cache layout [*, S_max, *, D] with S_max >= s: take the
            # first s rows.  neox caches duplicate each frequency
            # interleaved (s0,s0,s1,s1,...) — de-interleave; half-split
            # caches repeat the half — take the first half
            sn_full = d(sin).reshape(-1, hd)[:s]
            cs_full = d(cos).reshape(-1, hd)[:s]
            if use_neox_rotary_style:
                sn, cs = sn_full[:, 0::2], cs_full[:, 0::2]
            else:
                sn, cs = sn_full[:, : hd // 2], cs_full[:, : hd // 2]
            if position_ids is not None:
                raise ValueError(
                    "pass either position_ids or precomputed sin/cos "
                    "(gather the cache by position yourself)")
            sn = jnp.broadcast_to(sn[None], (b, s, hd // 2))
            cs = jnp.broadcast_to(cs[None], (b, s, hd // 2))
        sn = sn[:, :, None, :]
        cs = cs[:, :, None, :]
        if use_neox_rotary_style:
            x1, x2 = tt[..., 0::2], tt[..., 1::2]
            r1 = x1 * cs - x2 * sn
            r2 = x2 * cs + x1 * sn
            return jnp.stack([r1, r2], axis=-1).reshape(tt.shape)
        half = hd // 2
        x1, x2 = tt[..., :half], tt[..., half:]
        return jnp.concatenate([x1 * cs - x2 * sn,
                                x2 * cs + x1 * sn], axis=-1)

    outs = [apply_op("fused_rope", rope_pure, (t,), {})
            if t is not None else None for t in (q, k, v)]
    return tuple(outs)


def swiglu(x, y=None, name=None):
    """SwiGLU activation (reference incubate swiglu op); differentiable."""
    import jax

    def pure(xx, yy):
        if yy is None:
            a, b = jnp.split(xx, 2, axis=-1)
        else:
            a, b = xx, yy
        return jax.nn.silu(a) * b

    return apply_op("swiglu", pure, (x, y), {})


def ragged_decode_attention(q, k_cache, v_cache, lengths,
                            use_pallas=None, interpret=False):
    """Single-token decode attention over a ragged KV cache (GQA-aware).

    q [B, Nq, D]; k_cache/v_cache [B, S_max, Nkv, D] with Nq % Nkv == 0
    (query heads grouped contiguously per KV head); lengths [B] = valid
    prefix.  Uses the Pallas kernel
    (ops/pallas/decode_attention_kernel.py) in interpret mode when the
    shapes qualify, else the dense masked XLA fallback — identical
    semantics.  ``use_pallas=True`` on a TPU raises the kernel's
    ``TPU_REFUSAL``: it does not compile there yet.
    """
    from ...ops.pallas import decode_attention_kernel as dk

    def pure(qq, kk, vv, ll):
        import jax as _jax

        b, nq, d = qq.shape
        s_max, nkv = kk.shape[1], kk.shape[2]
        ok = dk.supports(s_max, d, nq, nkv) and (
            interpret or _jax.default_backend() == "tpu")
        # interpret mode (numerics-verified) auto-selects the kernel
        use = (interpret and ok) if use_pallas is None \
            else (use_pallas and ok)
        if use and not interpret:
            raise NotImplementedError(dk.TPU_REFUSAL)
        if use:
            return dk.decode_attention_pallas(qq, kk, vv, ll,
                                              interpret=True)
        return dk.decode_attention_xla(qq, kk, vv, ll)

    return apply_op("ragged_decode_attention", pure,
                    (q, k_cache, v_cache, lengths), {})


# coverage-table registration for the dispatched fused ops (names appear
# in the registry even though their public entry points live here)
for _name, _fn in [("swiglu", swiglu),
                   ("fused_rotary_position_embedding",
                    fused_rotary_position_embedding),
                   ("fused_ec_moe", fused_ec_moe),
                   ("fused_bias_dropout_residual_layer_norm",
                    fused_bias_dropout_residual_layer_norm),
                   ("ragged_decode_attention", ragged_decode_attention)]:
    register_external(_name, _fn, tags=("fused",))
