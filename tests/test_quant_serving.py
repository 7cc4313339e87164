"""Quantized serving: weight-only int8 GEMM + int8 paged KV pool.

The load-bearing claims: (1) ``LLMEngine(quantize="int8")`` stores the
four block GEMM weights int8 with per-output-channel scale siblings
that dequantize back to the f32 weights within quantization error, and
the int8 KV pool halves-and-then-some the per-page residency; (2) the
quantized engine serves end-to-end — generate, preempt, migrate —
with tp=2 bit-identical to tp=1 (scale sharding commutes with
dequant); (3) the memory model prices int8 residency, so the SAME
declared HBM budget admits at least 2x the batch; (4) int8 KV is
approximate by design, so the quality harness (perplexity + top-k
agreement) quantifies the delta instead of pretending token-exactness;
(5) the T001 dtype lint accepts intentional int8 leaves in a quantized
graph but still fires on a genuine float64 leak, with a dequant-
specific message for the int8 -> f64 widening accident; and (6) the
``QuantizedLinear`` deployment layer dequantizes in its stored
``out_dtype`` with no float32 round-trip, per-tensor (1, 1) scales
included.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import analysis as A
from paddle_tpu.inference.llm.quant import (
    QUANT_BLOCK_LEAVES,
    ServingQuantConfig,
    dequantize_kv_rows,
    quantize_kv_rows,
    quantize_weight,
    scale_key,
)


def _make_model(num_layers=2, seed=0):
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(seed)
    m = gpt_tiny(num_layers=num_layers)
    m.eval()
    return m


def _make_engine(m=None, quantize="int8", **kw):
    from paddle_tpu.inference.llm import LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("token_budget", 16)
    return LLMEngine(m if m is not None else _make_model(),
                     quantize=quantize, **kw)


def _prompts(n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (int(rng.randint(3, 12)),))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
class TestQuantConfig:
    def test_resolve_forms(self):
        assert ServingQuantConfig.resolve(None) is None
        c = ServingQuantConfig.resolve("int8")
        assert c.weights and c.kv_cache and c.bits == 8
        c2 = ServingQuantConfig.resolve({"weights": True,
                                         "kv_cache": False})
        assert c2.weights and not c2.kv_cache
        assert ServingQuantConfig.resolve(c) is c

    def test_resolve_quant_config_duck_type(self):
        from paddle_tpu.quantization import QuantConfig

        c = ServingQuantConfig.resolve(QuantConfig())
        assert c.weights and c.kv_cache

    def test_bad_specs_raise(self):
        with pytest.raises(ValueError, match="int8"):
            ServingQuantConfig.resolve("fp4")
        with pytest.raises(ValueError, match="no-op"):
            ServingQuantConfig(weights=False, kv_cache=False)
        with pytest.raises(ValueError, match="bits"):
            ServingQuantConfig(bits=4)
        with pytest.raises(TypeError):
            ServingQuantConfig.resolve(17)


# ---------------------------------------------------------------------------
class TestQuantPrimitives:
    def test_weight_roundtrip_per_output_channel(self):
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(2, 64, 192).astype(np.float32))
        q, s = quantize_weight(w)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert s.shape == (2, 1, 192)       # one scale per output col
        err = np.abs(np.asarray(q, np.float32) * np.asarray(s)
                     - np.asarray(w))
        # symmetric round-to-nearest: error bounded by half a step
        assert np.all(err <= np.asarray(s) * 0.5 + 1e-7)

    def test_kv_rows_roundtrip_and_zero_rows(self):
        rng = np.random.RandomState(1)
        v = jnp.asarray(rng.randn(5, 4, 16).astype(np.float32))
        v = v.at[2].set(0.0)                 # an all-zero token row
        q, s = quantize_kv_rows(v)
        assert q.dtype == jnp.int8 and s.shape == (5, 4)
        back = dequantize_kv_rows(q, s)
        np.testing.assert_allclose(np.asarray(back), np.asarray(v),
                                   atol=float(np.max(np.asarray(s)))
                                   * 0.5 + 1e-7)
        assert np.all(np.asarray(q[2]) == 0)
        assert np.all(np.asarray(back[2]) == 0.0)


# ---------------------------------------------------------------------------
class TestQuantEngine:
    def test_param_leaves_and_scales(self):
        eng = _make_engine()
        blocks = jax.device_get(eng.params)["blocks"]
        for key in QUANT_BLOCK_LEAVES:
            assert blocks[key].dtype == np.int8, key
            assert scale_key(key) in blocks, key
        # pool is int8 with f32 scale pools beside it
        cache = eng.kv_cache
        assert cache["k"].dtype == jnp.int8
        assert cache["k_scale"].dtype == jnp.float32
        assert cache["k_scale"].shape == (eng.num_layers, eng.num_blocks,
                                          eng.num_heads, eng.block_size)

    def test_unquantized_engine_untouched(self):
        eng = _make_engine(quantize=None)
        blocks = jax.device_get(eng.params)["blocks"]
        for key in QUANT_BLOCK_LEAVES:
            assert blocks[key].dtype == np.float32
            assert scale_key(key) not in blocks
        assert set(eng.kv_cache) == {"k", "v"}

    def test_dequantized_weights_close_to_f32(self):
        m = _make_model()
        ref = _make_engine(m, quantize=None)
        eng = _make_engine(m)
        rb = jax.device_get(ref.params)["blocks"]
        qb = jax.device_get(eng.params)["blocks"]
        for key in QUANT_BLOCK_LEAVES:
            s = qb[scale_key(key)]
            deq = qb[key].astype(np.float32) * s
            assert np.all(np.abs(deq - rb[key]) <= s * 0.5 + 1e-7), key

    def test_generate_smoke_and_finish(self):
        eng = _make_engine()
        prompts = _prompts()
        outs = eng.generate(prompts, max_new_tokens=8)
        for p, o in zip(prompts, outs):
            assert len(o) <= len(p) + 8
            np.testing.assert_array_equal(o[:len(p)], p)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_tp2_token_exact_vs_tp1(self):
        assert len(jax.devices()) >= 2
        m = _make_model()
        e1 = _make_engine(m)
        e2 = _make_engine(m, tensor_parallel=2)
        prompts = _prompts(seed=3)
        o1 = e1.generate(prompts, max_new_tokens=8)
        o2 = e2.generate(prompts, max_new_tokens=8)
        for a, b in zip(o1, o2):
            np.testing.assert_array_equal(a, b)

    def test_weight_only_mode_serves(self):
        eng = _make_engine(quantize={"weights": True,
                                     "kv_cache": False})
        assert eng.kv_cache["k"].dtype == eng.dtype   # pool stays f32
        assert "k_scale" not in eng.kv_cache
        outs = eng.generate(_prompts(n=2), max_new_tokens=6)
        assert len(outs) == 2

    def test_kv_only_mode_serves(self):
        eng = _make_engine(quantize={"weights": False,
                                     "kv_cache": True})
        blocks = jax.device_get(eng.params)["blocks"]
        assert blocks["attn.qkv.weight"].dtype == np.float32
        assert eng.kv_cache["k"].dtype == jnp.int8
        outs = eng.generate(_prompts(n=2), max_new_tokens=6)
        assert len(outs) == 2

    def test_no_new_compiles_after_warmup(self):
        eng = _make_engine()
        watcher = eng.warmup()
        eng.generate(_prompts(n=4, seed=5), max_new_tokens=8)
        assert watcher.new_compiles() == []


# ---------------------------------------------------------------------------
class TestQuantMemoryModel:
    def test_page_bytes_shrink(self):
        m = _make_model()
        mm32 = _make_engine(m, quantize=None).memory_model()
        mm8 = _make_engine(m).memory_model()
        assert mm8["kv_quantized"] is True
        assert mm32["kv_quantized"] is False
        # slot: head_dim + 4 vs head_dim * 4 (f32) = 20 vs 64 bytes
        assert mm8["page_bytes"] * 3 < mm32["page_bytes"]
        assert mm8["weights_bytes"] < mm32["weights_bytes"]

    def test_same_budget_admits_at_least_double(self):
        m = _make_model()
        mm32 = _make_engine(m, quantize=None).memory_model()
        budget = mm32["weights_bytes"] + int(2.5 * mm32["seq_bytes"])
        base = _make_engine(m, quantize=None, memory_budget=budget,
                            max_batch=64).max_batch
        quant = _make_engine(m, memory_budget=budget,
                             max_batch=64).max_batch
        assert base == 2
        assert quant >= 2 * base

    def test_engine_page_bytes_matches_model(self):
        eng = _make_engine()
        assert eng.page_bytes == eng.memory_model()["page_bytes"]


# ---------------------------------------------------------------------------
class TestQuantMigration:
    def test_export_import_resumes_token_exact(self):
        """Mid-decode handoff between two QUANTIZED engines: the int8
        pages AND their scale pools travel, so the merged outputs equal
        one unmigrated quantized engine bitwise."""
        from paddle_tpu.inference.llm import Fleet

        m = _make_model()
        ref = _make_engine(m)
        prompts = _prompts(n=3)
        want = ref.generate(prompts, max_new_tokens=10)

        fleet = Fleet(m, replicas=2, block_size=8, max_batch=4,
                      max_model_len=64, token_budget=16,
                      quantize="int8")
        e0 = fleet.replicas[0].engine
        e1 = fleet.replicas[1].engine
        rids = [e0.add_request(p, max_new_tokens=10) for p in prompts]
        outs = {}
        for _ in range(4):
            for fo in e0.step():
                outs[fo.request_id] = fo
        mover = rids[1]
        state = e0.export_request(mover)
        assert "k_scales" in state and "v_scales" in state
        e1.import_request(state["request"], state["seq"],
                          state["k_pages"], state["v_pages"],
                          k_scales=state["k_scales"],
                          v_scales=state["v_scales"])
        e0.release_request(mover)
        while e0.has_unfinished() or e1.has_unfinished():
            for fo in e0.step() + e1.step():
                outs[fo.request_id] = fo
        for rid, w in zip(rids, want):
            np.testing.assert_array_equal(outs[rid].all_ids, w)

    def test_scale_payload_mismatch_raises(self):
        from paddle_tpu.inference.llm import Fleet

        m = _make_model()
        fleet = Fleet(m, replicas=2, block_size=8, max_batch=4,
                      max_model_len=64, token_budget=16,
                      quantize="int8")
        e0, e1 = (r.engine for r in fleet.replicas)
        rid = e0.add_request(_prompts(n=1)[0], max_new_tokens=8)
        for _ in range(3):
            e0.step()
        state = e0.export_request(rid)
        # dropping the scale payload on a quantized import must fail
        # loudly, not silently attend over garbage scales
        before = e1.block_manager.num_free_blocks
        with pytest.raises(ValueError, match="scale"):
            e1.import_request(state["request"], state["seq"],
                              state["k_pages"], state["v_pages"])
        assert e1.block_manager.num_free_blocks == before

    def test_quant_to_unquant_import_rejected(self):
        m = _make_model()
        e0 = _make_engine(m)
        e1 = _make_engine(m, quantize=None)
        rid = e0.add_request(_prompts(n=1)[0], max_new_tokens=8)
        for _ in range(3):
            e0.step()
        state = e0.export_request(rid)
        with pytest.raises(ValueError):
            e1.import_request(state["request"], state["seq"],
                              state["k_pages"], state["v_pages"],
                              k_scales=state["k_scales"],
                              v_scales=state["v_scales"])


# ---------------------------------------------------------------------------
class TestQualityHarness:
    def test_self_report_is_perfect(self):
        from paddle_tpu.inference.llm.quality import quality_report

        eng = _make_engine(quantize=None)
        rep = quality_report(eng, eng, [[1, 2, 3], [7, 8, 9, 10]],
                             max_new_tokens=6)
        assert rep["greedy_agreement"] == 1.0
        assert rep["top1_agreement"] == 1.0
        assert rep["perplexity_delta"] == 0.0

    def test_quant_vs_ref_finite_and_documented(self):
        import math

        from paddle_tpu.inference.llm.quality import quality_report

        m = _make_model()
        ref = _make_engine(m, quantize=None)
        eng = _make_engine(m)
        rep = quality_report(ref, eng, _prompts(n=3, seed=9),
                             max_new_tokens=8, top_k=5)
        for k in ("perplexity_ref", "perplexity_test",
                  "perplexity_delta", "top1_agreement",
                  "topk_agreement", "greedy_agreement"):
            assert math.isfinite(rep[k]), k
        assert 0.0 <= rep["topk_agreement"] <= 1.0
        assert rep["positions"] > 0

    def test_dense_logits_match_engine_argmax(self):
        from paddle_tpu.inference.llm.quality import engine_logits

        eng = _make_engine(quantize=None)
        prompt = [1, 2, 3, 4]
        out = eng.generate([prompt], max_new_tokens=4)[0]
        logits = engine_logits(eng, out)
        assert int(np.argmax(logits[len(prompt) - 1])) == out[len(prompt)]

    def test_tp_engine_rejected(self):
        from paddle_tpu.inference.llm.quality import engine_logits

        assert len(jax.devices()) >= 2
        eng = _make_engine(tensor_parallel=2)
        with pytest.raises(ValueError, match="tp=1"):
            engine_logits(eng, [1, 2, 3])


# ---------------------------------------------------------------------------
class TestQuantDtypeLint:
    def test_quant_grid_t001_clean(self):
        """int8 params and pools in the quantized executables are
        intentional — the dtype lint must produce no findings."""
        eng = _make_engine()
        fs = A.analyze_engine(eng, rules=("T001",))
        assert fs == [], [f.format() for f in fs]

    def test_quant_grid_all_rules_clean(self):
        eng = _make_engine()
        fs = A.analyze_engine(eng)
        assert fs == [], [f.format() for f in fs]

    def test_f64_leak_in_quantized_graph_still_fires(self):
        """Seeded bug: a float64 scale in the dequant multiply of an
        otherwise-int8 graph must fire T001, including the dequant-
        specific int8 -> f64 widening message."""
        import jax.numpy as jnp

        def bad_dequant(q, s64):
            return q.astype(jnp.float64) * s64

        with jax.enable_x64(True):
            closed = jax.make_jaxpr(bad_dequant)(
                jax.ShapeDtypeStruct((8, 16), jnp.int8),
                jax.ShapeDtypeStruct((1, 16), jnp.float64))
        fs = A.check_dtypes(closed, label="quant")
        assert any(f.rule == "T001" for f in fs)
        assert any("dequantize in the activation dtype" in f.message
                   for f in fs)


# ---------------------------------------------------------------------------
class TestQuantizedLinearDeployment:
    """Satellite: the QAT/PTQ deployment layer's forward must
    dequantize via its stored out_dtype without a float32 round-trip,
    and per-tensor (1, 1) scales must broadcast."""

    def _linear(self, dtype, in_f=8, out_f=16, seed=0):
        import paddle_tpu.nn as nn

        paddle.seed(seed)
        lin = nn.Linear(in_f, out_f)
        if dtype != jnp.float32:
            lin.weight._data = lin.weight._data.astype(dtype)
            lin.bias._data = lin.bias._data.astype(dtype)
        return lin

    def test_per_tensor_scale_regression(self):
        from paddle_tpu.quantization import QuantizedLinear

        lin = self._linear(jnp.float32)
        w = np.asarray(lin.weight._data)
        scale = float(np.abs(w).max())
        ql = QuantizedLinear(lin, scale)          # scalar -> (1, 1)
        assert ql.scales._data.shape == (1, 1)
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(4, 8).astype(np.float32))
        got = ql(x).numpy()
        want = np.asarray(lin(x).numpy())
        # int8 per-tensor quantization error bound
        assert np.max(np.abs(got - want)) <= scale / 127 * 8 + 1e-5

    def test_bf16_out_dtype_no_f32_roundtrip(self):
        from paddle_tpu.quantization import QuantizedLinear

        lin = self._linear(jnp.bfloat16)
        w = np.asarray(lin.weight._data.astype(jnp.float32))
        scales = np.abs(w).max(axis=0)
        ql = QuantizedLinear(lin, scales, channel_axis=-1)
        assert ql.out_dtype == jnp.bfloat16
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        x._data = x._data.astype(jnp.bfloat16)
        out = ql(x)
        assert out._data.dtype == jnp.bfloat16
        # the dequantized weight itself must be built in out_dtype —
        # no float32 intermediate anywhere in the forward graph
        forward_src = str(jax.make_jaxpr(
            lambda xx: ql.forward(xx)._data)(x._data))
        assert "f64" not in forward_src
        assert "f32[8,16]" not in forward_src, \
            "forward materializes a float32 dequantized weight"


# ---------------------------------------------------------------------------
def test_bench_quant_gated_row(tmp_path):
    """tier-1 smoke of ``bench_serving.py --quant int8``: the gated
    acceptance row must pass its own contract (baseline preempts, int8
    runs 2x the admissible batch under the same budget with zero
    preemptions, token-count-exact, zero leaks, zero post-warmup
    compiles, finite quality deltas) and write an ok=true artifact."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_quant.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--quant", "int8", "--artifact", artifact],
        capture_output=True, text=True, timeout=420, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    with open(artifact) as f:
        art = json.load(f)
    assert art["ok"] is True
    row = art["bench"]
    assert row["metric"] == "llm_serving_quant"
    assert row["base_preemptions"] > 0
    assert row["preemptions"] == 0
    assert row["quant_max_batch"] == 2 * row["base_max_batch"]
    assert row["token_count_exact"] is True
    assert row["leaked_pages"] == 0 and row["base_leaked_pages"] == 0
    assert row["new_compiles"] == 0
    assert row["topk_agreement"] >= 0.0
    assert row["quant_page_bytes"] < row["base_page_bytes"]
