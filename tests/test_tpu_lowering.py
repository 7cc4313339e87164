"""Every registered Pallas kernel against the TPU compiler, from the CPU.

The kernels run here only in interpret mode, which accepts block shapes
the TPU lowering refuses.  Two screens keep "passes in interpret mode"
from meaning "has never met Mosaic":

- tier-1: cross-lower each registry entry for platform ``tpu`` on this
  host (the Pallas->Mosaic block-shape rules run; no TPU needed) at the
  shapes a GPT-124M-width and a head_dim-128 engine launch, plus the
  training shapes.  An entry lowers wherever its ``supports()`` let the
  case through, unless it declares a ``tpu_refusal`` — and then it must
  really fail, so the refusal is deleted with the fault;
- slow: the full Mosaic compile of the same cases for a compile-only
  v5e topology, which also enforces the alignment proofs lowering
  cannot see (dynamic sublane offsets on packed dtypes).
"""

import functools
import re
import types

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.inference.llm import LLMEngine
from paddle_tpu.ops.pallas import registry
from paddle_tpu.ops.pallas.layernorm_kernel import layernorm_pallas


def _engine_like(num_heads, head_dim, dtype):
    """What the ``engine_shapes`` builders read off an engine, at the
    sizes ``chip_smoke.py`` serves with, without allocating weights or
    pools.  ``_kv_quant`` is on so the int8 entry yields its cases too
    (the full-precision entries ignore it)."""
    e = types.SimpleNamespace(
        num_heads=num_heads, head_dim=head_dim,
        hidden=num_heads * head_dim, tp=1, dtype=jnp.dtype(dtype),
        block_size=16, max_batch=8, max_model_len=1024, max_pages=64,
        num_blocks=8 * 64, token_budget=256, _kv_quant=True)
    e._bucket_grid = functools.partial(LLMEngine._bucket_grid, e)
    return e


def _train_layernorm_cases():
    """Fused layernorm at the GPT-124M training activations (the
    registry's own layernorm cases are float32 at serving rows)."""
    def vjp(x, g, b):
        def loss(*a):
            return jnp.sum(layernorm_pallas(*a).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(x, g, b)

    sds = jax.ShapeDtypeStruct
    w = sds((768,), jnp.bfloat16)
    for seq in (1024, 512):
        x = sds((8, seq, 768), jnp.bfloat16)
        yield registry.KernelCase(f"train_vjp[8x{seq}x768]", vjp,
                                  (x, w, w), None)


def _cases():
    """(id, entry, case) for every registry entry at every engine."""
    engines = {"gpt124m-bf16": _engine_like(12, 64, jnp.bfloat16),
               "gpt124m-f32": _engine_like(12, 64, jnp.float32),
               "hd128-bf16": _engine_like(32, 128, jnp.bfloat16)}
    for name, entry in sorted(registry.load_all().items()):
        for ename, eng in engines.items():
            for case in entry.engine_shapes(eng):
                yield f"{name}/{ename}/{case.label}", entry, case
        if name == "layernorm":
            for case in _train_layernorm_cases():
                yield f"{name}/{case.label}", entry, case


def test_registry_lowers_for_tpu_where_supported():
    lowered = 0
    for cid, entry, case in _cases():
        traced = jax.jit(case.fn).trace(*case.args)
        if entry.tpu_refusal is not None:
            with pytest.raises(Exception, match="last two dimensions"):
                traced.lower(lowering_platforms=("tpu",))
            continue
        try:
            text = traced.lower(lowering_platforms=("tpu",)).as_text()
        except Exception as e:      # noqa: BLE001 — name the case
            pytest.fail(f"{cid}: supports() says yes, the TPU lowering "
                        f"says no: {str(e).splitlines()[0]}")
        assert "tpu_custom_call" in text, cid
        lowered += 1
    # ragged + ragged_quant over 6 buckets x 3 engines, flash and
    # layernorm fwd+vjp x 3, flash at 192 | 128 (latent attention's
    # expanded form) fwd+vjp on the head_dim-128 engine, flash under a
    # window over one kv head and under a block-diffusion mask of 4 over
    # one kv head vjp x 3 each, the block-window-plus-summaries
    # (eva) kernels vjp x 3, the two training layernorm shapes, the
    # state-space scan's kernels vjp x 3, latent attention's expansion
    # (value and vjp) x 3, the expert layer's run sums (weighted and not)
    # x 3, the causal convolution's kernels (value and vjp) x 3, the gated
    # group norm's (value and vjp) x 3, compressed convolutional attention's
    # mix (value and vjp) x 3
    assert lowered == 2 * 6 * 3 + 2 * 3 + 2 + 3 + 3 + 3 + 2 * 3 + 2 + 3 + 3 \
        + 2 * 3 + 2 * 3 + 2 * 3 + 2 * 3


def test_refusals_are_declared_only_where_needed():
    refused = {n for n, e in registry.load_all().items()
               if e.tpu_refusal is not None}
    assert refused == {"decode_attention"}


def test_gspmd_partitioning_is_seen_at_trace_time():
    """JAX refuses a Mosaic kernel in a computation GSPMD partitions
    ("cannot be automatically partitioned"); the dispatchers must know
    before they pick one: off under a multi-device mesh, on again
    inside a shard_map that makes every axis manual."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.ops.pallas import _partitioned_by_gspmd

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    seen = {}

    def probe(tag):
        def f(x):
            seen[tag] = _partitioned_by_gspmd()
            return x
        return f

    x = jnp.ones((4,))
    jax.jit(probe("plain jit"))(x)
    with use_mesh(mesh):
        jax.jit(probe("under the mesh"))(x)
    jax.jit(jax.shard_map(probe("all axes manual"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P("dp"),
                          check_vma=False))(x)
    jax.jit(jax.shard_map(probe("one axis manual"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P("dp"),
                          axis_names={"dp"}, check_vma=False))(x)
    assert seen == {"plain jit": False, "under the mesh": True,
                    "all axes manual": False, "one axis manual": True}


def test_flash_under_the_dp_mp_mesh_lowers_for_tpu(monkeypatch):
    """The four-chip cell's attention, forward and backward, under the
    mesh ``SpmdTrainStep`` enters: the dispatcher, on its kernel path,
    must hand the TPU lowering something it takes.  "Mosaic kernels cannot
    be automatically partitioned" is raised while lowering, so a CPU can
    hold this guard: the kernel called bare under the same mesh is the
    control that it still would be."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas.attention_kernel import flash_attention_pallas

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    mesh = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    assert tuple(mesh.shape.values()) == (2, 1, 1, 2)
    x = jax.ShapeDtypeStruct(
        (4, 2048, 32, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

    def grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x)

    with use_mesh(mesh):
        text = grads(lambda q, k, v: pk.flash_attention(
            q, k, v, is_causal=True)).lower(
                lowering_platforms=("tpu",)).as_text()
        with pytest.raises(NotImplementedError,
                           match="cannot be automatically partitioned"):
            grads(lambda q, k, v: flash_attention_pallas(
                q, k, v, True)).lower(lowering_platforms=("tpu",))
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    assert text.count("tpu_custom_call") == 2


def _gpt3_width(**kw):
    """GPT-3 6.7B's widths (4096, 32 heads of 128, context 2048), amp O2."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig

    paddle.seed(0)
    return GPTConfig(vocab_size=512, hidden_size=4096, num_layers=1,
                     num_attention_heads=32, max_position_embeddings=2048,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0, **kw)


@pytest.mark.parametrize("remat,forwards", [(True, 1), ("full", 2)],
                         ids=["tagged", "full"])
def test_dp_mp_step_with_the_viewed_qkv_lowers_for_tpu(monkeypatch, remat,
                                                       forwards):
    """The four-chip cell's whole step (one layer of it: the layers are a
    scan) at ``[4, 2048]`` tokens under dp=2 x mp=2, rematerialised, on
    the kernel path: the trainer holds the fused q|k|v viewed ``[L, h, 3,
    heads, head_dim]``, heads over ``mp``, the TPU lowering takes the step
    with the flash kernels inside, and NO tensor of the program is 12,288
    wide any more: neither activation, gradient nor weight is ever in the
    form whose halves are no set of heads.  Under ``remat=True`` (the
    cell's) the flash forward's tagged results are kept and the layer body
    holds the forward kernel ONCE; under ``"full"`` it runs again in the
    backward pass, twice a layer."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.distributed.fleet.topology import build_mesh
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.parallel import SpmdTrainStep

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    model = paddle.amp.decorate(GPTForCausalLM(_gpt3_width()), level="O2",
                                dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=1.2e-4,
                          parameters=model.parameters())
    mesh = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    trainer = SpmdTrainStep(model, opt, mesh, remat=remat)
    held = trainer.params["blocks"]["attn.qkv.weight"]
    assert held.shape == (1, 4096, 3, 32, 128)
    assert {s.data.shape for s in held.addressable_shards} \
        == {(1, 4096, 3, 16, 128)}
    ids = np.zeros((4, 2048), np.int32)
    args = trainer._operands(1, jax.ShapeDtypeStruct((2,), jnp.uint32),
                             ids, ids)
    with use_mesh(mesh):
        text = trainer._compiled.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "flash_attention_fwd"') == forwards
    assert text.count('kernel_name = "flash_attention_bwd_dq_dkv"') == 1
    assert "1x4096x3x32x128xbf16" in text
    assert "12288" not in text
    # the boundary still speaks the stored layout
    assert trainer.state_dict()["params"]["blocks"][
        "attn.qkv.weight"].shape == (1, 4096, 12288)


def test_one_chip_qkv_projection_stays_one_dot(monkeypatch):
    """``GPTAttention`` forward + grad on one chip, the leaf stored ``[h,
    3h]``: the projection is the single ``[.., 4096] x [4096, 12288]`` dot
    it was (the head-structured product is for the viewed leaf alone: on
    one chip XLA compiled it to a slower step, PERF.md section 6, PR 29),
    and the layer has its five matmuls: q|k|v forward, input gradient and
    weight gradient, the output projection's two gradients (its forward
    falls out of a sum's gradient)."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models.gpt import GPTAttention
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)  # flash: no dots
    attn = paddle.amp.decorate(GPTAttention(_gpt3_width()), level="O2",
                               dtype="bfloat16")
    p = {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
         for k, v in attn.state_dict().items()}
    x = jax.ShapeDtypeStruct((4, 2048, 4096), jnp.bfloat16)

    def loss(p, x):
        return jnp.sum(functional_call(attn, p, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(p, x).lower(
        lowering_platforms=("tpu",)).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*?: \((.*?)\) -> (\S+)",
                      text)
    assert len(dots) == 5, dots
    assert dots.count(("tensor<4x2048x4096xbf16>, tensor<4096x12288xbf16>",
                       "tensor<4x2048x12288xbf16>")) == 1, dots
    assert "3x32x128x" not in "".join(d[0] for d in dots)


def _case_results(text):
    """The result types of every ``stablehlo.case`` of a lowered text."""
    import re

    return re.findall(r"^ *\}\) : \(tensor<i32>\) -> (.*)$", text, re.M)


def test_a_small_buckets_branch_holds_no_worst_case_residual(monkeypatch):
    """The expert layer's routed block, forward + grad, lowered for the
    TPU at a small width: 4,096 tokens, 4 of 48 experts held, a bucket of
    3,072 rows before the worst case's 16,384.  What leaves
    the two switches (one each way) has the TOKENS' rows or a parameter's
    shape: no branch hands a ``[bucket rows, .]`` array on, the worst
    case's least of all, and the grouped matmuls are the Pallas calls in
    every branch.  Differentiated THROUGH, the same switch hands on every
    branch's residuals at once (each branch writes zeros for the others'):
    that is what the one ``custom_vjp`` round the block is for."""
    import functools

    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe import (
        DroplessMoELayer, dropless)
    from paddle_tpu.jit import functional_call
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    tokens, top_k, experts, held, h = 4096, 4, 48, 4, 128
    buckets = dropless.row_buckets(tokens, top_k, held, experts)
    assert buckets == (3072, 16384)
    paddle.seed(0)
    layer = paddle.amp.decorate(
        DroplessMoELayer(h, 128, experts, top_k, num_local_experts=held,
                         expert_offset=3), level="O2", dtype="bfloat16")
    p = {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
         for k, v in layer.state_dict().items()}
    x = jax.ShapeDtypeStruct((1, tokens, h), jnp.bfloat16)

    def loss(p, x):
        return jnp.sum(functional_call(layer, p, x).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(p, x).lower(
        lowering_platforms=("tpu",)).as_text()
    results = _case_results(text)
    assert len(results) == 2, results
    assert not any(f"<{rows}x" in r for rows in buckets for r in results), \
        results
    assert f"tensor<{tokens}x{h}xbf16>" in results[0]
    # 2 grouped matmuls forward and 2 + 4 backward, and the rows' way back
    # to the tokens (``moe_run_sum``) once each way, in each branch
    assert text.count("tpu_custom_call") == 10 * len(buckets)
    assert "ragged_dot" not in text

    # differentiated through, the block is its XLA compositions (the run
    # sums' kernel has no rule of its own: the block's custom_vjp is its)
    monkeypatch.setattr(pk, "_use_pallas", lambda: False)

    def through(x, gate_up, down, order, w_sorted, here, counts):
        operands = (x, gate_up, down, order, w_sorted, here, counts)
        return jnp.sum(jax.lax.switch(
            dropless.bucket_of(counts, buckets),
            [functools.partial(dropless._routed_fwd_rows, "swiglu", top_k,
                               rows)
             for rows in buckets], *operands).astype(jnp.float32))

    sds = jax.ShapeDtypeStruct
    operands = (sds((tokens, h), jnp.bfloat16),
                p["experts.gate_up"], p["experts.down"],
                sds((buckets[-1],), jnp.int32),
                sds((buckets[-1],), jnp.float32),
                sds((tokens,), jnp.int32), sds((held,), jnp.int32))
    naive = _case_results(jax.jit(jax.grad(through, argnums=(0, 1, 2)))
                          .trace(*operands).lower(
                              lowering_platforms=("tpu",)).as_text())
    assert any(f"<{buckets[-1]}x" in r for r in naive), naive


def test_sdars_routed_block_moves_nothing_sized_by_its_assignments(
        monkeypatch):
    """The routed block at the sdar cell's shapes (16,384 positions, top-8,
    16 of 128 experts held, rows of 2,048), value and gradients, lowered
    for the TPU in its small bucket of 32,768 rows: between the router and
    the tokens again no gather and no scatter takes ``A = k S`` = 131,072
    indices and no ``[131072, 2048]`` buffer exists (PR 44's tree: one such
    gather each way and a scalar gather and a scatter of 131,072); what is
    sized by ``A`` is the sort, its payload and elementwise integer ops."""
    import re

    from paddle_tpu.incubate.distributed.models.moe import dropless
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    tokens, top_k, held, experts, h, inner = 16384, 8, 16, 128, 2048, 768
    buckets = dropless.row_buckets(tokens, top_k, held, experts)
    assert buckets == (32768, 131072)
    sds = jax.ShapeDtypeStruct
    operands = (sds((tokens, h), jnp.bfloat16),
                sds((tokens, top_k), jnp.float32),
                sds((held, h, 2 * inner), jnp.bfloat16),
                sds((held, inner, h), jnp.bfloat16),
                sds((tokens, top_k), jnp.int32))

    def loss(x, weights, w_in, w_out, idx):
        # the small bucket's branch of both switches, alone
        return jnp.sum(dropless.routed_experts(
            x, weights, w_in, w_out, idx, 0, buckets[:1])
            .astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).trace(
        *operands).lower(lowering_platforms=("tpu",)).as_text()
    assert f"{tokens * top_k}x{h}" not in text
    gathers = re.findall(
        r'"stablehlo\.gather"\(.*?\) <\{.*?\}> : \(tensor<(\S+?)>, '
        r'tensor<(\d+)x1xi32>\)', text)
    # the rows: x[token] forward and backward, g[token], the rows into
    # token order and one row a token, combine and the dispatch's transpose
    rows = sorted(int(n) for of, n in gathers if of.endswith(f"x{h}xbf16"))
    assert rows == [tokens] * 2 + [buckets[0]] * 5, gathers
    assert max(int(n) for _, n in gathers) == buckets[0]
    scatters = re.findall(
        r"\}\) : \(tensor<(\S+?)>, tensor<(\d+)x1xi32>, tensor<\S+?>\) -> ",
        text)
    assert (f"{tokens * top_k}xf32", str(buckets[0])) in scatters    # d_w
    assert max(int(n) for _, n in scatters) == buckets[0], scatters
    # the sort by expert with its two payloads, the sort by token each way
    assert text.count("stablehlo.sort") == 3
    # 2 + 6 grouped matmuls and the two run sums
    assert text.count("tpu_custom_call") == 10
    assert text.count('kernel_name = "moe_run_sum"') == 2


# The flash kernels as Mosaic gets them (the module inside each
# ``tpu_custom_call``, its source locations stripped: they shift with
# every edit to the kernel file and are no part of the program), hashed, at
# the two shapes the benchmark's cells call them with.  The FORWARD's were
# read at PR 29's tree (eff4393) and are unchanged by PR 30, which taught
# the kernels windows and grouped KV heads, and by PR 33, which made the
# backward ONE kernel and did not touch the forward: a call with neither
# window nor groups runs the forward program it ran.  The backward's were
# read at PR 33's tree: the module that replaced ``flash_attention_bwd_dq``
# and ``flash_attention_bwd_dkv``.  PR 35 moved the two FORWARD modules: the
# forward writes the log-sum-exp as a lane-dense row of a ``[seq / block_q,
# block_q]`` block where it wrote a ``[block_q, 1]`` column (the dots, the
# masks and the accumulation are the ones they were).  The backward's are
# PR 33's still: a call with one kv head a q head keeps the copy layout and
# its index maps (``tests/test_flash_layout.py``).  A new JAX may print a
# module
# differently; then read them again at a tree whose kernels are known to be
# these (``_mosaic_bodies`` of the same calls).
_PLAIN_FLASH_BODIES = {
    # gpt3-6.7b-train*.seq2048: [4, 2048, 32, 128] bf16, causal
    ((4, 2048, 32, 128), 128): {
        "flash_attention_fwd": "821740d3ac794335",
        "flash_attention_bwd_dq_dkv": "4ed72c333b785416"},
    # kanana-2-30b-a3b-train-ep8.seq8192: [2, 8192, 32, 192 | 128]
    ((2, 8192, 32, 192), 128): {
        "flash_attention_fwd": "093204ef0e2897a9",
        "flash_attention_bwd_dq_dkv": "342e0ec67b6eeb2c"},
}


def _mosaic_bodies(text):
    """{kernel name: hash of its Mosaic module without locations} of a
    lowered text's ``tpu_custom_call``s."""
    import base64
    import hashlib
    import re

    from jaxlib.mlir import ir

    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name = "([^"]+)"', line).group(1)
        blob = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                         line).group(1)
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(blob), ctx).operation.get_asm(
            enable_debug_info=False)
        out[name] = hashlib.sha256(asm.encode()).hexdigest()[:16]
    return out


def _flash_grads_lowered(q, k, v, **kw):
    from paddle_tpu.ops.pallas.attention_kernel import flash_attention_pallas

    def f(q, k, v):
        def loss(*a):
            return jnp.sum(flash_attention_pallas(
                *a, is_causal=True, **kw).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return jax.jit(f).trace(q, k, v).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("shape,v_dim", list(_PLAIN_FLASH_BODIES))
def test_plain_flash_calls_lower_as_at_the_parent(shape, v_dim):
    """No window, equal head counts: the GPT cells' and kanana's flash
    calls hand Mosaic the pinned modules (the backward's as before the
    kernels knew of windows and groups; the forward's as PR 35 left them,
    which changed how the statistics leave)."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (v_dim,), jnp.bfloat16)
    assert _mosaic_bodies(_flash_grads_lowered(q, q, v)) \
        == _PLAIN_FLASH_BODIES[(shape, v_dim)]
    # a window that covers the sequence is no window
    assert _mosaic_bodies(_flash_grads_lowered(q, q, v, window=shape[1])) \
        == _PLAIN_FLASH_BODIES[(shape, v_dim)]


def test_window_and_grouped_calls_are_other_programs():
    """Laguna's two calls: their own names and their own modules,
    and K and V at eight heads (``[1, 8192, 8 * 128]``, where ``k_proj``
    and ``v_proj`` leave them) all the way into the custom calls."""
    q72 = jax.ShapeDtypeStruct((1, 8192, 72, 128), jnp.bfloat16)
    q48 = jax.ShapeDtypeStruct((1, 8192, 48, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    window = _mosaic_bodies(_flash_grads_lowered(q72, kv, kv, window=512))
    full = _mosaic_bodies(_flash_grads_lowered(q48, kv, kv))
    assert set(window) == {f"flash_window512_attention_{k}"
                           for k in ("fwd", "bwd_dq_dkv")}
    assert set(full) == {f"flash_attention_{k}"
                         for k in ("fwd", "bwd_dq_dkv")}
    known = {h for bodies in _PLAIN_FLASH_BODIES.values()
             for h in bodies.values()}
    assert not known & (set(window.values()) | set(full.values()))
    assert "tensor<1x8192x1024xbf16>" in _flash_grads_lowered(q48, kv, kv)


def _scoped_vmem(text, kernel):
    """The bytes of VMEM a custom call of ``text`` asks for, or nothing."""
    line = next(l for l in text.splitlines()
                if f'kernel_name = "{kernel}"' in l)
    asked = re.search(r'scoped_memory_configs.*?\\22size\\22: (\d+)', line)
    return int(asked.group(1)) if asked else None


@pytest.mark.parametrize("seq,asked", [(8192, None), (16384, 32 << 20)])
def test_a_causal_forward_asks_for_vmem_from_a_row_of_16384(seq, asked):
    """The forward holds a kv head's K and V whole, double-buffered: 8 MB at
    8,192 (nothing asked: laguna's call as it was), 16 MB at 16,384, which
    is Mosaic's whole default allowance, so the call asks for what it holds
    and 16 MB more (the v5e compiler refused the zaya cell's step
    without: "Scoped allocation with size 16.62M and limit 16.00M")."""
    q = jax.ShapeDtypeStruct((1, seq, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
    assert _scoped_vmem(_flash_grads_lowered(q, kv, kv),
                        "flash_attention_fwd") == asked


def test_eva_kernels_lower_as_before_the_flash_backward_was_one_kernel():
    """``eva_attention_bwd_dkv`` ran ``attention_kernel._bwd_dkv_kernel``,
    which PR 33 took away with the flash dq kernel; the block-window
    kernels keep a dk/dv kernel of their own (their dq runs a joint softmax
    over exact keys and summaries) and hand Mosaic the four modules they
    did at evabyte's shape (read at PR 32's tree, 0522594)."""
    from paddle_tpu.ops.pallas.eva_attention_kernel import (
        eva_attention_pallas)

    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    s = jax.ShapeDtypeStruct((1, 1024, 32, 128), jnp.bfloat16)

    def f(*operands):
        def loss(*a):
            return jnp.sum(eva_attention_pallas(
                *a, window=2048, chunk=16).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*operands)

    text = jax.jit(f).trace(x, x, x, s, s).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _mosaic_bodies(text) == {
        "eva_attention_fwd": "723736c00c92473e",
        "eva_attention_bwd_dq": "1e689f20db398c30",
        "eva_attention_bwd_dkv": "b5dfebcf1290a243",
        "eva_attention_bwd_dkv_summaries": "9debdc4d878fceb5"}


# the flash calls of the benchmark's cells: (q, kv, v width, window, the
# benchmark's counter of calls and the mark it looks for)
_CELL_FLASH_CALLS = {
    "gpt3-6.7b": ((4, 2048, 32, 128), 32, 128, None, "mla", None),
    "kanana-mla": ((2, 8192, 32, 192), 32, 128, None, "mla", None),
    "laguna-full": ((1, 8192, 48, 128), 8, 128, None, "gqa",
                    "flash_attention"),
    "laguna-window": ((1, 8192, 72, 128), 8, 128, 512, "gqa",
                      "flash_window"),
    # a group of 7, the first that is no power of two, in a row of 16,384
    "smallthinker-full": ((1, 16384, 28, 128), 4, 128, None, "gqa",
                          "flash_attention"),
    "smallthinker-window": ((1, 16384, 28, 128), 4, 128, 4096, "gqa",
                            "flash_window"),
}


def _cell_flash_lowered(case):
    shape, kv_heads, v_dim, window, _, _ = _CELL_FLASH_CALLS[case]
    b, t, _, h = shape
    return _flash_grads_lowered(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        jax.ShapeDtypeStruct((b, t, kv_heads, h), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, t, kv_heads, v_dim), jnp.bfloat16),
        window=window)


@pytest.mark.parametrize("case", list(_CELL_FLASH_CALLS))
def test_a_flash_backward_is_one_custom_call(case):
    """Forward and gradients of one flash call, lowered for the TPU: TWO
    ``tpu_custom_call``s, the forward and the ONE backward (it was three:
    dq and dk/dv formed the scores and dP a second time)."""
    import re

    text = _cell_flash_lowered(case)
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    window = _CELL_FLASH_CALLS[case][3]
    stem = ("flash_attention" if window is None
            else f"flash_window{window}_attention")
    assert sorted(names) == [f"{stem}_bwd_dq_dkv", f"{stem}_fwd"]
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("case", list(_CELL_FLASH_CALLS))
def test_the_benchmark_counts_one_backward_call_by_the_kernels_names(case):
    """The naming contract with ``chipbench/kernel_costs``: a trace names
    a kernel's event ``<kernel_name>.<n> <shape>``; the rooflines count
    events that hold ``_fwd`` as forward calls and events that hold
    ``_bwd_dq`` as backward calls, and ``trace.kernel_seconds`` charges
    every event whose name holds the metric's pattern.  Fed the names this
    tree lowers to, the benchmark's own counters find one forward and one
    backward a flash call, and every event's time is charged."""
    import re
    import types

    from chipbench.kernel_costs import (flash_attention_gqa,
                                        flash_attention_mla)

    _, _, _, window, counter, mark = _CELL_FLASH_CALLS[case]
    names = re.findall(r'kernel_name = "([^"]+)"', _cell_flash_lowered(case))
    calls = 3
    events = [(f"{name}.{i} bf16[8,8192,128]", 0.0, 1.0)
              for i in range(calls) for name in names]
    events.append(("fusion.7 f32[8,8192,1]", 0.0, 1.0))
    env = types.SimpleNamespace(traced={"devices": {0: events}})
    if counter == "mla":
        counted = flash_attention_mla.calls_in_window(env)
    else:
        counted = flash_attention_gqa.calls_in_window(env, mark)
    assert counted == (calls, calls)
    pattern = "flash_window" if window else "flash_attention"
    assert all(pattern in name for name in names)
    if window is None:      # a full call is no window call, nor the reverse
        assert flash_attention_gqa.calls_in_window(
            env, "flash_window") == (0, 0)
    else:
        assert flash_attention_mla.calls_in_window(env) == (0, 0)


@pytest.mark.parametrize("layer_idx,stem", [
    (0, "flash_attention"), (1, "flash_window4096_attention")])
def test_a_published_smallthinker_layer_lowers_with_every_operand_in_place(
        monkeypatch, layer_idx, stem):
    """One block of SmallThinker-21BA3B at its published widths and the
    cell's share (28 q heads over 4 kv heads of 128, 16 of 64 ReGLU experts,
    six a token, a row of 16,384), value and gradients, lowered for the TPU:
    layer 0 is full attention with no rotation, layer 1 a window of 4,096
    with one.  Both flash calls are the kernels with all eight operands in
    place, the routed block's way back to its tokens is the ``moe_run_sum``
    kernel in every branch, and the router's matmul reads the block's INPUT
    (a ``[16384, 2560] x [2560, 64]`` float32 dot whose operand is the
    function's argument, cast)."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import moe_decoder
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    config = SmallThinkerConfig(
        vocab_size=512, hidden_size=2560, num_hidden_layers=4,
        num_attention_heads=28, num_key_value_heads=4, head_dim=128,
        moe_ffn_hidden_size=768, moe_num_primary_experts=64,
        moe_num_active_primary_experts=6, sliding_window_size=4096,
        num_local_experts=16)
    paddle.seed(0)
    block = paddle.amp.decorate(moe_decoder.MoeDecoderLayer(config, layer_idx),
                                level="O2", dtype="bfloat16")
    p = {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
         for k, v in block.state_dict().items()}
    x = jax.ShapeDtypeStruct((1, 16384, 2560), jnp.bfloat16)

    def loss(p, x):
        out, counts, rows, unserved = functional_call(block, p, x)
        return jnp.sum(out.astype(jnp.float32) ** 2), (counts, unserved)

    before = pk.traced_call_sums()
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)) \
        .trace(p, x).lower(lowering_platforms=("tpu",)).as_text()
    sums = {k: v - before.get(k, 0) for k, v in pk.traced_call_sums().items()}
    assert sums["flash_calls"] == 1 and sums["flash_operands_in_place"] == 8
    assert not sums.get("flash_operands_copied")
    assert sums["moe_run_sum_calls"] == 4           # two buckets, each way
    assert not sums.get("moe_run_sum_calls_composed")
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert sorted(set(n for n in names if "flash" in n)) \
        == [f"{stem}_bwd_dq_dkv", f"{stem}_fwd"]
    assert names.count("moe_run_sum") == 4
    assert "ragged_dot" not in text
    # layer 0 rotates nothing: no cosine table is baked into its program
    tables = re.findall(r"stablehlo\.constant .*tensor<16384x64xf32>", text)
    assert len(tables) == (2 if layer_idx == 1 else 0)


# ------------------------------------------- latent attention's expansion --

# batch, seq, heads, nope, rope, v, dtype: the kanana cell's call, and two
# more that ``mla_expand_kernel.supports`` takes (a head a whole tile in
# float32; four heads a step at a rotary width of 32)
_MLA_EXPAND_CALLS = {
    "kanana": (2, 8192, 32, 128, 64, 128, jnp.bfloat16),
    "rope128-f32": (1, 1024, 16, 128, 128, 128, jnp.float32),
    "rope32": (1, 512, 8, 128, 32, 256, jnp.bfloat16),
}


def _mla_expand_lowered(case, interleave=True):
    from paddle_tpu.ops.pallas.mla_expand_kernel import (mla_expand_pallas,
                                                         supports)

    b, t, n, nope, rope, v_dim, dtype = _MLA_EXPAND_CALLS[case]
    assert supports(t, n, nope, rope, v_dim, dtype)
    sds = jax.ShapeDtypeStruct
    table = sds((t, rope // 2), jnp.float32)

    def f(q, kv_b, k_rope, cos, sin):
        def loss(*o):
            return sum(jnp.sum(x.astype(jnp.float32))
                       for x in mla_expand_pallas(
                           *o, cos, sin, nope=nope, interleave=interleave))
        # the map is linear: its gradients alone need no forward
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, kv_b, k_rope)

    return jax.jit(f).trace(
        sds((b, t, n, nope + rope), dtype), sds((b, t, n, nope + v_dim),
                                                dtype),
        sds((b, t, rope), dtype), table, table).lower(
            lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("case", list(_MLA_EXPAND_CALLS))
def test_mla_expand_is_one_kernel_forward_and_one_backward(case):
    """Forward and gradients of latent attention's expansion, lowered for
    the TPU at the cell's shapes and wherever ``supports()`` says yes: TWO
    ``tpu_custom_call``s, whose names the flash rooflines do not count
    (``chipbench/kernel_costs/flash_attention_mla.py`` counts every event
    that holds ``flash_attention``)."""
    import re
    import types

    from chipbench.kernel_costs import flash_attention_mla

    text = _mla_expand_lowered(case)
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["mla_expand_bwd", "mla_expand_fwd"]
    assert text.count("tpu_custom_call") == 2
    events = [(f"{name}.{i} bf16[2,32,8192,192]", 0.0, 1.0)
              for i in range(3) for name in names]
    env = types.SimpleNamespace(traced={"devices": {0: events}})
    assert flash_attention_mla.calls_in_window(env) == (0, 0)


def test_mla_expand_lowers_in_halves_too():
    """``rope_interleave`` false is the same two kernels under other lane
    maps and tables: operands, not another program."""
    assert _mosaic_bodies(_mla_expand_lowered("kanana", interleave=False)) \
        == _mosaic_bodies(_mla_expand_lowered("kanana"))


@pytest.fixture
def mla_expand_on_tpu(monkeypatch):
    """What the dispatcher sees on the chip: kernels on, the backend's name
    ``tpu``; the kernels themselves in interpret mode."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import mla_expand_kernel as mk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mk, "mla_expand_pallas", functools.partial(
        mk.mla_expand_pallas, interpret=True))
    return pk


def _mla_expand_operands(heads, nope, rope, v_dim, seq=32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shapes = [(1, seq, heads, nope + rope), (1, seq, heads, nope + v_dim),
              (1, seq, rope)]
    table = jnp.ones((seq, rope // 2), jnp.float32)
    return [jax.random.normal(k, s, jnp.float32).astype(jnp.bfloat16)
            for k, s in zip(ks, shapes)] + [table, 0.5 * table]


@pytest.mark.parametrize("case", ["kernel", "off_the_tpu", "width_off_tiles",
                                  "mesh"])
def test_mla_expand_dispatch_is_counted_and_gives_way_aloud(
        case, request):
    """``ops.pallas.mla_expand_qkv``: the kernels where ``supports()`` says
    yes on a TPU; the composition, with a warning that says why, for a
    width off the 128-lane tiles and under a mesh GSPMD partitions; the
    composition without a word off the TPU.  Every call is in
    ``mla_expand_log()`` and in ``traced_call_sums()``'s two counts."""
    import warnings

    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.distributed.fleet.spmd import use_mesh
    from paddle_tpu.ops import pallas as pk

    if case != "off_the_tpu":
        request.getfixturevalue("mla_expand_on_tpu")
    geometry = (4, 32, 16, 32) if case == "width_off_tiles" \
        else (4, 128, 64, 128)
    operands = _mla_expand_operands(*geometry)
    call = functools.partial(pk.mla_expand_qkv, *operands, nope=geometry[1],
                             interleave=True)
    before = pk.traced_call_sums()
    if case in ("kernel", "off_the_tpu"):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = call()
        reason = None if case == "kernel" else "no TPU backend"
    elif case == "width_off_tiles":
        with pytest.warns(pk.KernelFallbackWarning,
                          match="mla_expand.*supports"):
            got = call()
        reason = "mla_expand_kernel.supports() refuses the shape"
    else:
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        with use_mesh(mesh), pytest.warns(
                pk.KernelFallbackWarning,
                match="mla_expand.*GSPMD cannot partition a Mosaic kernel"):
            got = jax.eval_shape(call)
        reason = pk.GSPMD_REASON
    after = pk.traced_call_sums()
    rec = pk.mla_expand_log()[-1]
    assert rec["path"] == ("kernel" if case == "kernel" else "composition")
    assert rec["reason"] is reason is None or reason in rec["reason"]
    assert rec["shapes"] == (tuple(operands[0].shape),
                             tuple(operands[1].shape))
    assert {k: after[k] - before[k] for k in after} == {
        "flash_calls": 0, "flash_operands_in_place": 0,
        "flash_operands_copied": 0, "ssd_calls": 0, "ssd_calls_composed": 0,
        "moe_run_sum_calls": 0, "moe_run_sum_calls_composed": 0,
        "causal_conv_calls": 0, "causal_conv_calls_composed": 0,
        "gated_norm_calls": 0, "gated_norm_calls_composed": 0,
        "cca_mix_calls": 0, "cca_mix_calls_composed": 0,
        "mla_expand_calls": 1,
        "mla_expand_calls_composed": 0 if case == "kernel" else 1}
    if case != "mesh":
        want = pk._xla_mla_expand_qkv(*operands, nope=geometry[1],
                                      interleave=True)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


# ------------------------------------------ the Mamba mixer's gated norm --

# The ``gated_norm_*`` kernels as Mosaic gets them at the hybrid cell's call
# (y ``bf16[1, 4096, 8192]`` over 8 groups, the gate the first 8,192 lanes of
# the projection's ``[1, 4096, 18560]``), source locations stripped
# (``_mosaic_bodies``); read at PR 48's tree.
_GATED_NORM_BODIES = {"gated_norm_fwd": "62813a11718d5111",
                      "gated_norm_bwd": "0530d9f17d30e112"}


def _gated_norm_lowered(wide, start):
    from paddle_tpu.ops.pallas.gated_norm_kernel import (gated_norm_pallas,
                                                         supports)

    dtype = jnp.bfloat16
    assert supports(4096, 8192, 8, dtype, start)
    norm = functools.partial(gated_norm_pallas, groups=8, epsilon=1e-5,
                             start=start)
    sds = jax.ShapeDtypeStruct

    def f(y, z, w):
        def loss(*o):
            return jnp.sum(norm(*o).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(y, z, w)

    return jax.jit(f).trace(
        sds((1, 4096, 8192), dtype), sds((1, 4096, wide), dtype),
        sds((8192,), dtype)).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("wide,start,same", [
    (18560, 0, True), (8192, 0, True), (18560, 10240, False)],
    ids=["in_place", "sliced", "behind_other_lanes"])
def test_the_gated_norm_is_one_kernel_forward_and_one_backward(wide, start,
                                                               same):
    """Value and gradients of the mixer's gated norm, lowered for the TPU at
    the cell's shape: TWO ``tpu_custom_call``s and no op shaped by the view
    ``[..., groups, width]``.  The gate read in place at the head of the
    projection's result and a gate sliced out before the call are ONE
    program (the operand's width is none of the kernel's business); a gate
    that lies behind other lanes is the same program under another block
    offset."""
    text = _gated_norm_lowered(wide, start)
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["gated_norm_bwd", "gated_norm_fwd"]
    assert text.count("tpu_custom_call") == 2
    assert "x8x1024x" not in text
    assert (_mosaic_bodies(text) == _GATED_NORM_BODIES) == same


# ------------------------- compressed convolutional attention's mix --

# The ``cca_mix_*`` kernels as Mosaic gets them at the zaya cell's call (q~
# ``bf16[1, 16384, 8, 128]`` over k~, v ``[1, 16384, 2, 128]``, two taps each,
# 64 dims rotated), source locations stripped (``_mosaic_bodies``); read at
# PR 50's tree.
_CCA_MIX_BODIES = {"cca_mix_fwd": "14059de0043b4fbc",
                   "cca_mix_bwd": "cc3d0fc11112d692"}


def _cca_mix_args(dtype=jnp.bfloat16, seq=16384, n=8, kv=2, d=128, rot=64):
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    return (sds((1, seq, n, d), dtype), sds((1, seq, kv, d), dtype),
            sds((1, seq, kv, d), dtype), sds((n * d, 2), dtype),
            sds((n, 2, d, d), dtype), sds((kv * d, 2), dtype),
            sds((kv, 2, d, d), dtype), sds((kv,), f32),
            sds((seq, rot // 2), f32), sds((seq, rot // 2), f32))


def _cca_mix_value_and_grads(*o):
    from paddle_tpu.ops.pallas.cca_mix_kernel import cca_mix_pallas

    def loss(*o):
        return sum(jnp.sum(x.astype(jnp.float32))
                   for x in cca_mix_pallas(*o, epsilon=1e-5))
    return jax.value_and_grad(loss, argnums=tuple(range(8)))(*o)


def test_the_cca_mix_is_one_kernel_forward_and_one_backward():
    """Values and all eight gradients of the mix, lowered for the TPU at
    the cell's shape: TWO ``tpu_custom_call``s, which take q~, k~ and v as
    ``[1, 16384, heads * 128]`` (the projections' results, reshaped) and
    give q^, k^, v' the same way (the flash kernels' in-place layout): no
    operand of either is a ``[.., heads, 128]`` view."""
    from paddle_tpu.ops.pallas.cca_mix_kernel import supports

    assert supports(16384, 8, 2, 128, (2, 2), 64, jnp.bfloat16)
    text = jax.jit(_cca_mix_value_and_grads).trace(*_cca_mix_args()).lower(
        lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["cca_mix_bwd", "cca_mix_fwd"]
    assert text.count("tpu_custom_call") == 2
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    for line in calls:
        assert "tensor<1x16384x1024xbf16>" in line \
            and "tensor<1x16384x256xbf16>" in line
        assert "16384x8x128x" not in line and "16384x2x128x" not in line
    assert _mosaic_bodies(text) == _CCA_MIX_BODIES


@pytest.mark.slow
def test_registry_compiles_for_v5e():
    """The real Mosaic compile, ahead of time, for a device this host
    does not have.  libtpu prints a TPU_ACCELERATOR_TYPE warning and
    carries on; where it cannot describe the topology, skip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no usable libtpu
        pytest.skip(f"no compile-only TPU topology: {e}")
    dev = SingleDeviceSharding(topo.devices[0])
    assert topo.devices[0].device_kind == "TPU v5 lite"
    for cid, entry, case in _cases():
        if entry.tpu_refusal is not None:
            continue
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)
                for a in case.args]
        try:
            jax.jit(case.fn).lower(*args).compile()
        except Exception as e:      # noqa: BLE001 — name the case
            pytest.fail(f"{cid}: Mosaic refuses: "
                        f"{str(e).splitlines()[0]}")


@pytest.mark.slow
def test_the_trainers_options_make_all_reduces_asynchronous_for_v5e():
    """``parallel.trainer.ASYNC_ALL_REDUCE`` against the compiler it is
    written for, ahead of time: a scan of two small rematerialised blocks
    (column- then row-parallel matmuls over ``mp``, the batch over ``dp``)
    with its update, on a ``("dp", "mp")`` 2 x 2 mesh of described v5e
    chips.  Without the options every all-reduce runs in line; with them
    the backward body holds an async collective fusion.  A libtpu that
    renames an option fails HERE ("No such compile option") and not in a
    cell; where no topology can be described, skip."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.trainer import (ASYNC_ALL_REDUCE,
                                             compile_options,
                                             compiled_collectives)

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no usable libtpu
        pytest.skip(f"no compile-only TPU topology: {e}")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    assert compile_options(mesh) == ASYNC_ALL_REDUCE

    def sds(shape, *spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.bfloat16, sharding=NamedSharding(mesh, P(*spec)))

    layers, hidden, inner = 2, 1024, 4096
    params = {"w1": sds((layers, hidden, inner), None, None, "mp"),
              "w2": sds((layers, inner, hidden), None, "mp", None)}
    x = sds((4, 512, hidden), "dp", None, None)

    @jax.checkpoint
    def block(p, h):
        y = jax.nn.gelu(jnp.einsum("bth,hi->bti", h, p["w1"]))
        return h + jnp.einsum("bti,ih->bth", y, p["w2"])

    def loss(params, x):
        h, _ = jax.lax.scan(lambda h, p: (block(p, h), None), x, params)
        return jnp.mean(h.astype(jnp.float32) ** 2)

    def step(params, x):
        value, grads = jax.value_and_grad(loss)(params, x)
        return value, jax.tree_util.tree_map(
            lambda p, g: p - 1e-3 * g, params, grads)

    def compiled_text(options):
        return jax.jit(step, donate_argnums=0, compiler_options=options
                       ).lower(params, x).compile().as_text()

    plain = compiled_collectives(compiled_text(None))
    assert plain and not any(asynchronous for _, _, asynchronous in plain)
    text = compiled_text(compile_options(mesh))
    found = compiled_collectives(text)
    assert len(found) == len(plain)     # the same collectives, each once
    hidden_ones = [shapes for _, shapes, asynchronous in found
                   if asynchronous]
    # the mp reduction of a block's input gradient rides the weight
    # gradient's matmul, inside the scan's backward body (not in ENTRY)
    assert [(2, 512, hidden)] in hidden_ones
    assert "async-collective-start" in text.split("\nENTRY ")[0]
