"""Cost engine (framework/cost.py): static FLOPs/HBM/comms + census.

Three load-bearing halves:

- parity: the static walker's FLOP/transcendental counts must agree
  with XLA's own HloCostAnalysis exactly on closed-form graphs and
  within 5% on every shipped serving bucket (XLA folds some address
  arithmetic the walker cannot see);
- seeded-bug battery: one intentional violation per census rule —
  M001 (per-chip HBM over budget), C001 (loop-invariant collective /
  psum-of-psum), B001 (executable-count blowup) — each MUST fire;
- golden census: the census's static compile count must equal the
  number of compiles CompileWatcher observes during warmup(), at tp=1
  and tp=2 and with speculative decoding, and the census itself must
  leave every serving cache cold.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import cost as C
from paddle_tpu.framework.analysis import CompileWatcher

SDS = jax.ShapeDtypeStruct


def _make_engine(tp=None, **kw):
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(0)
    m = gpt_tiny(num_layers=2)
    m.eval()
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("token_budget", 16)
    return LLMEngine(m, tensor_parallel=tp, **kw)


def _mesh2():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]), ("mp",))


# ---------------------------------------------------------------------------
class TestUnits:
    def test_parse_bytes(self):
        assert C.parse_bytes(1024) == 1024
        assert C.parse_bytes("512") == 512
        assert C.parse_bytes("16GiB") == 16 * 1024 ** 3
        assert C.parse_bytes("1.5 MiB") == int(1.5 * 1024 ** 2)
        assert C.parse_bytes("2GB") == 2 * 10 ** 9
        assert C.parse_bytes(None) is None

    def test_parse_bytes_rejects_junk(self):
        with pytest.raises(ValueError, match="memory size"):
            C.parse_bytes("sixteen gigs")

    def test_derive_max_batch(self):
        # budget 100, weights 40, seq 25 -> floor(60/25) == 2
        assert C.derive_max_batch(100, 40, 25) == 2

    def test_derive_max_batch_too_tight_raises(self):
        with pytest.raises(ValueError, match="budget"):
            C.derive_max_batch(50, 40, 25)


# ---------------------------------------------------------------------------
class TestFlopParity:
    """The static walker vs XLA's HloCostAnalysis."""

    def test_matmul_tanh_exact(self):
        def f(a, b):
            return jnp.tanh(a @ b) + 1.0

        a, b = SDS((128, 256), jnp.float32), SDS((256, 64), jnp.float32)
        est = C.estimate_jitted(f, a, b)
        xla = C.xla_cost_analysis(f, a, b)
        assert est.flops == xla["flops"]
        assert est.transcendentals == xla["transcendentals"]

    def test_scan_loop_aware_vs_xla_parity(self):
        """XLA costs a scan body ONCE; the loop-aware walk multiplies
        by length.  Both views come from one walk."""
        def g(xs):
            def body(c, x):
                c = jnp.tanh(c @ x)
                return c, c.sum()
            return jax.lax.scan(body, jnp.ones((64, 64)), xs)

        xs = SDS((4, 64, 64), jnp.float32)
        est = C.estimate_jitted(g, xs)
        xla = C.xla_cost_analysis(g, xs)
        assert est.flops == pytest.approx(4 * est.flops_xla_parity,
                                          rel=0.01)
        assert est.flops_xla_parity == pytest.approx(xla["flops"],
                                                     rel=0.001)

    def test_engine_ragged_buckets_within_5pct(self):
        eng = _make_engine()
        checked = 0
        for kind, bucket, fn, args in eng.executable_grid():
            assert kind == "ragged"
            est = C.estimate_jitted(fn, *args, loop_aware=False)
            xla = C.xla_cost_analysis(fn, *args)
            rel = abs(est.flops - xla["flops"]) / max(xla["flops"], 1)
            assert rel <= 0.05, (kind, bucket, est.flops, xla["flops"])
            checked += 1
        assert checked == 2

    def test_engine_speculative_grid_identical(self):
        """speculative=K no longer adds a verify family: draft scoring
        rides the same ragged buckets, so the grid is the tp=1 grid."""
        eng = _make_engine(speculative=2)
        grid = [(kind, bucket)
                for kind, bucket, _, _ in eng.executable_grid()]
        assert grid == [("ragged", 8), ("ragged", 16)]
        for kind, bucket, fn, args in eng.executable_grid():
            est = C.estimate_jitted(fn, *args, loop_aware=False)
            xla = C.xla_cost_analysis(fn, *args)
            rel = abs(est.flops - xla["flops"]) / max(xla["flops"], 1)
            assert rel <= 0.05, (kind, bucket, est.flops, xla["flops"])

    def test_roofline_classification(self):
        est = C.CostEstimate()
        est.flops = 10 ** 15
        est.hbm_bytes = 10 ** 6
        r = est.roofline("tpu-v4")
        assert r["bound"] == "compute"
        est2 = C.CostEstimate()
        est2.flops = 10 ** 6
        est2.hbm_bytes = 10 ** 12
        assert est2.roofline("tpu-v4")["bound"] == "hbm"


# ---------------------------------------------------------------------------
class TestPeakLiveness:
    def test_donation_lowers_peak(self):
        """Donating the input lets XLA alias it into the output; the
        static peak must drop by (at least) the donated buffer."""
        def f(x):
            return x * 2.0 + 1.0

        x = SDS((1024,), jnp.float32)
        plain = C.estimate_jitted(f, x)
        donated = C.estimate_jitted(jax.jit(f, donate_argnums=0), x)
        assert donated.peak_bytes <= plain.peak_bytes - x.dtype.itemsize

    def test_peak_covers_intermediates(self):
        """Peak must count live intermediates, not just the boundary."""
        def f(a, b):
            big = a @ b            # 128x128 intermediate
            return big.sum()

        a, b = SDS((128, 64), jnp.float32), SDS((64, 128), jnp.float32)
        est = C.estimate_jitted(f, a, b)
        assert est.peak_bytes >= (128 * 64 + 64 * 128 + 128 * 128) * 4


# ---------------------------------------------------------------------------
class TestC001Seeded:
    """Collective-placement lint fires on its intentional violations
    and stays silent on the legitimate per-iteration pattern."""

    def test_loop_invariant_psum_in_scan(self):
        from jax.sharding import PartitionSpec as P

        def bad(xs, w):
            def body(c, x):
                s = jax.lax.psum(w, "mp")     # hoistable out of scan
                return c + x * s.sum(), None
            c, _ = jax.lax.scan(body, jnp.zeros(xs.shape[1:]), xs)
            return c

        f = jax.shard_map(bad, mesh=_mesh2(), in_specs=(P(), P()),
                          out_specs=P(), check_vma=False)
        closed = jax.jit(f).trace(jnp.ones((4, 2)), jnp.ones((2,))).jaxpr
        fs = C.check_collectives(closed, label="seeded")
        assert [f.rule for f in fs] == ["C001"]
        assert "loop-invariant" in fs[0].message

    def test_redundant_psum_of_psum(self):
        from jax.sharding import PartitionSpec as P

        def bad(x):
            return jax.lax.psum(jax.lax.psum(x, "mp"), "mp")

        f = jax.shard_map(bad, mesh=_mesh2(), in_specs=P(),
                          out_specs=P(), check_vma=False)
        closed = jax.jit(f).trace(jnp.ones((2,))).jaxpr
        fs = C.check_collectives(closed)
        assert [f.rule for f in fs] == ["C001"]
        assert "redundant" in fs[0].message

    def test_carry_dependent_psum_is_clean(self):
        """The shipped per-layer pattern: the reduced value depends on
        the loop carry, so it is NOT hoistable and must not fire."""
        from jax.sharding import PartitionSpec as P

        def good(xs):
            def body(c, x):
                c = c + jax.lax.psum(c * x, "mp")
                return c, None
            c, _ = jax.lax.scan(body, jnp.zeros(xs.shape[1:]), xs)
            return c

        f = jax.shard_map(good, mesh=_mesh2(), in_specs=P(),
                          out_specs=P(), check_vma=False)
        closed = jax.jit(f).trace(jnp.ones((4, 2))).jaxpr
        assert C.check_collectives(closed) == []


# ---------------------------------------------------------------------------
class TestCensus:
    def test_golden_census_matches_warmup_compiles_tp1(self):
        """The census's static compile count is the contract for
        warmup(): every bucket it enumerates compiles exactly once."""
        eng = _make_engine()
        cen = C.run_census(eng)
        assert cen.families == {"ragged": 2}
        w = CompileWatcher(eng._ragged)
        eng.warmup()
        observed = sum(n for _, n in w.new_compiles())
        assert cen.compile_count == observed == 2

    def test_golden_census_matches_warmup_compiles_speculative(self):
        # speculative no longer adds a family: same 2 ragged buckets
        eng = _make_engine(speculative=2)
        cen = C.run_census(eng)
        assert cen.families == {"ragged": 2}
        w = CompileWatcher(eng._ragged)
        eng.warmup()
        observed = sum(n for _, n in w.new_compiles())
        assert cen.compile_count == observed == 2

    def test_golden_census_matches_warmup_compiles_tp2(self):
        assert len(jax.devices()) >= 2
        eng = _make_engine(tp=2)
        cen = C.run_census(eng)
        w = CompileWatcher(eng._ragged)
        eng.warmup()
        observed = sum(n for _, n in w.new_compiles())
        assert cen.compile_count == observed == 2
        # tp=2 buckets must carry per-axis collective payloads
        assert all(e["cost"]["collective_bytes"].get("mp", 0) > 0
                   for e in cen.entries)

    def test_golden_census_matches_warmup_compiles_quant(self):
        """int8 serving keeps the ONE ragged executable family: the
        quantized engine's census must enumerate the same bucket count
        and match warmup's observed compiles exactly (the int8 pools
        and scale operands change signatures, not the grid)."""
        eng = _make_engine(quantize="int8")
        cen = C.run_census(eng)
        assert cen.families == {"ragged": 2}
        w = CompileWatcher(eng._ragged)
        eng.warmup()
        observed = sum(n for _, n in w.new_compiles())
        assert cen.compile_count == observed == 2

    def test_census_quant_clean(self):
        cen = C.run_census(_make_engine(quantize="int8"))
        assert cen.findings == [], [f.format() for f in cen.findings]

    def test_census_shipped_engine_clean_and_cold(self):
        """tier-1 CI gate: zero M001/C001 findings over the shipped
        grid (incl. speculative) and every serving cache stays COLD —
        the census uses the AOT trace path, never the dispatch path."""
        eng = _make_engine(speculative=2)
        cen = C.run_census(eng)
        assert cen.findings == [], [f.format() for f in cen.findings]
        assert eng._ragged._cache_size() == 0

    def test_census_tp2_clean(self):
        cen = C.run_census(_make_engine(tp=2))
        assert cen.findings == [], [f.format() for f in cen.findings]

    def test_m001_fires_on_tight_budget(self):
        eng = _make_engine()
        mm = C.engine_memory_model(eng)
        resident = mm["weights_bytes"] + mm["kv_pool_bytes"]
        cen = C.run_census(eng, memory_budget=resident // 2)
        m001 = [f for f in cen.findings if f.rule == "M001"]
        assert m001 and m001[0].severity == "error"
        # breakdown names both residency classes + the remedy
        assert "weights" in m001[0].message
        assert "pages" in m001[0].message
        assert "max_batch" in m001[0].message

    def test_m001_silent_on_adequate_budget(self):
        eng = _make_engine()
        mm = C.engine_memory_model(eng)
        cen = C.run_census(eng, memory_budget=2 * (
            mm["weights_bytes"] + mm["kv_pool_bytes"]))
        assert [f for f in cen.findings if f.rule == "M001"] == []

    def test_b001_fires_on_grid_blowup(self):
        cen = C.run_census(_make_engine(), max_executables=1)
        b001 = [f for f in cen.findings if f.rule == "B001"]
        assert b001 and "2 executables" in b001[0].message

    def test_census_to_json_roundtrip(self):
        import json

        doc = json.loads(C.run_census(_make_engine()).to_json())
        assert doc["compile_count"] == 2
        assert {"flops", "hbm_bytes", "peak_bytes", "roofline"} <= set(
            doc["entries"][0]["cost"]) | {"roofline"} | set(
            doc["entries"][0])


# ---------------------------------------------------------------------------
class TestEngineMemoryBudget:
    def test_budget_clamps_max_batch_and_pool(self):
        probe = _make_engine()
        mm = C.engine_memory_model(probe)
        budget = mm["weights_bytes"] + 2 * mm["seq_bytes"] + 100
        eng = _make_engine(memory_budget=budget)
        assert eng.max_batch == 2
        assert eng.num_blocks == 2 * eng.max_pages
        assert eng.scheduler.max_batch == 2

    def test_budget_accepts_unit_strings(self):
        eng = _make_engine(memory_budget="1GiB")
        assert eng.memory_budget == 1024 ** 3
        assert eng.max_batch == 4          # roomy: no clamp

    def test_budget_too_tight_raises(self):
        with pytest.raises(ValueError, match="budget"):
            _make_engine(memory_budget=1024)

    def test_budget_rejects_oversized_explicit_pool(self):
        probe = _make_engine()
        mm = C.engine_memory_model(probe)
        budget = mm["weights_bytes"] + 2 * mm["seq_bytes"] + 100
        with pytest.raises(ValueError, match="num_blocks"):
            _make_engine(memory_budget=budget, num_blocks=64)

    def test_clamped_engine_is_token_exact(self):
        """The budget clamp changes throughput, never tokens."""
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (3, 11, 6)]
        ref = _make_engine().generate(prompts, max_new_tokens=4)
        probe = _make_engine()
        mm = C.engine_memory_model(probe)
        budget = mm["weights_bytes"] + 2 * mm["seq_bytes"] + 100
        got = _make_engine(memory_budget=budget).generate(
            prompts, max_new_tokens=4)
        assert all(np.array_equal(a, b) for a, b in zip(ref, got))

    def test_memory_model_method(self):
        eng = _make_engine()
        mm = eng.memory_model("16GiB")
        assert mm["derived_max_batch"] >= eng.max_batch
        assert mm["kv_pool_bytes"] == mm["page_bytes"] * eng.num_blocks

    def test_quant_residency_doubles_admissible_batch(self):
        """M001's memory model prices int8 residency: the SAME declared
        budget that admits batch 2 at f32 must admit >= 4 quantized —
        both weight bytes (1 byte/param + scale rows on the four GEMM
        leaves) and page bytes (head_dim + 4 per slot) shrink."""
        mm32 = C.engine_memory_model(_make_engine())
        budget = mm32["weights_bytes"] + int(2.5 * mm32["seq_bytes"])
        base = _make_engine(memory_budget=budget, max_batch=64)
        quant = _make_engine(memory_budget=budget, max_batch=64,
                             quantize="int8")
        assert base.max_batch == 2
        assert quant.max_batch >= 2 * base.max_batch
        mm8 = C.engine_memory_model(quant)
        assert mm8["kv_quantized"] is True
        assert mm8["derived_max_batch"] >= 2 * base.max_batch
        # the model's page pricing matches the engine's own accounting
        assert mm8["page_bytes"] == quant.page_bytes
        hd = quant.head_dim
        assert mm8["page_bytes"] * (hd * 4) \
            == mm32["page_bytes"] * (hd + 4)
