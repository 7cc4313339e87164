"""Pallas kernel verifier (framework/kernel_lint.py, rules K001-K005).

Same two-halves contract as test_analysis.py:

- seeded-bug battery: one intentionally broken pallas_call per rule —
  misaligned lane tiling, VMEM-overflowing residency, index maps and
  in-body dynamic slices provably out of bounds, a write-race output
  map, and registry-contract violations (unregistered module, dead
  fallback, missing parity test) — each MUST fire its exact rule;
- clean sweeps: every registered kernel at every engine launch shape
  (tp=1 and tp=2) produces ZERO findings, without compiling a single
  serving executable, and ``supports()`` never admits a shape the
  verifier rejects.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu as paddle
from paddle_tpu.framework import analysis as A
from paddle_tpu.framework import kernel_lint as KL
from paddle_tpu.ops.pallas import registry

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


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
class TestSeededKernelBugs:
    """Each rule fires on its intentional violation, with a message a
    kernel author can act on."""

    def test_k001_lane_misalignment(self):
        # lane (last) dim 50: neither a multiple of 128 nor the full dim
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((16, 50), lambda i: (0, i))],
            out_specs=pl.BlockSpec((16, 50), lambda i: (0, i)),
            out_shape=SDS((16, 100), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((16, 100), jnp.float32))
        hits = [x for x in fs if x.rule == "K001"
                and x.category == "lane"]
        assert hits and hits[0].severity == "error"
        assert "128" in hits[0].message

    def test_k001_sublane_misalignment(self):
        # sublane 12 on f32: minimum tile is (8, 128) and 12 % 8 != 0
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((12, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((12, 128), lambda i: (i, 0)),
            out_shape=SDS((24, 128), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((24, 128), jnp.float32))
        assert any(x.rule == "K001" and x.category == "sublane"
                   for x in fs)

    def test_k001_grid_block_coverage(self):
        # 24 rows / block 16 with grid 2: last step hangs off the array
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((16, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
            out_shape=SDS((24, 128), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((24, 128), jnp.float32))
        assert any(x.rule == "K001" and x.category == "divisibility"
                   for x in fs)

    def test_k002_vmem_overflow_names_binding_buffer(self):
        # one (8, 524288) f32 block is 16 MiB; double-buffered in+out
        # quadruples it — far past the 16 MiB tpu-v4 budget
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((8, 524288), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 524288), lambda i: (0, 0)),
            out_shape=SDS((8, 524288), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((8, 524288), jnp.float32))
        hits = [x for x in fs if x.rule == "K002"
                and x.severity == "error"]
        assert hits and "binding buffer: x_ref" in hits[0].message
        assert str(16 * 1024 * 1024) in hits[0].message

    def test_k002_holds_a_call_to_the_allowance_it_asks(self):
        # the same copy under a vmem_limit_bytes of its own: 64 MiB
        # resident fits 80 MiB asked, and overflows 48
        def f(limit):
            return lambda x: pl.pallas_call(
                _copy_kernel, grid=(1,),
                in_specs=[pl.BlockSpec((8, 524288), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 524288), lambda i: (0, 0)),
                out_shape=SDS((8, 524288), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=limit << 20))(x)
        errors = lambda limit: [                       # noqa: E731
            x for x in KL.analyze_kernel(f(limit), SDS((8, 524288),
                                                       jnp.float32))
            if x.rule == "K002" and x.severity == "error"]
        assert not errors(80)
        assert errors(48) and str(48 << 20) in errors(48)[0].message

    def test_k002_respects_profile(self):
        blocks = [((8, 524288), jnp.float32)]
        assert not KL.vmem_fits(blocks, profile="tpu-v4")
        assert KL.vmem_fits([((8, 128), jnp.float32)], profile="tpu-v4")

    def test_k003_index_map_out_of_bounds(self):
        # input map runs j over [0, 15] but only 8 blocks of 8 rows exist
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(16,),
            in_specs=[pl.BlockSpec((8, 128), lambda j: (j, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda j: (j % 8, 0)),
            out_shape=SDS((64, 128), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((64, 128), jnp.float32))
        hits = [x for x in fs if x.rule == "K003"
                and x.category == "index-map"]
        assert hits and "[0, 15]" in hits[0].message
        assert "[0, 7]" in hits[0].message

    def test_k003_body_dynamic_slice_overrun(self):
        # the classic block_k*j overrun: pl.ds(pid*16, 16) reaches row 63
        # of a 32-row block on the last grid step
        def k(x_ref, o_ref):
            b = pl.program_id(0)
            o_ref[...] = x_ref[pl.ds(b * 16, 16), :]

        f = lambda x: pl.pallas_call(
            k, grid=(4,),
            in_specs=[pl.BlockSpec((32, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
            out_shape=SDS((64, 128), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((32, 128), jnp.float32))
        hits = [x for x in fs if x.rule == "K003"
                and x.category == "body-ds"]
        assert hits and "63" in hits[0].message
        assert "32" in hits[0].message

    def test_k003_in_bounds_ds_is_clean(self):
        def k(x_ref, o_ref):
            b = pl.program_id(0)
            o_ref[...] = x_ref[pl.ds(b * 8, 8), :]

        f = lambda x: pl.pallas_call(
            k, grid=(4,),
            in_specs=[pl.BlockSpec((32, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=SDS((32, 128), jnp.float32))(x)
        assert KL.analyze_kernel(f, SDS((32, 128), jnp.float32)) == []

    def test_k004_write_race_non_contiguous_revisit(self):
        # out block j under grid (2, 4): each j is written on grid steps
        # {j, j+4} — it is left and revisited, so the first write is lost
        # on TPU (last-writer-wins) but visible in interpret mode
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[0]

        f = lambda x: pl.pallas_call(
            k, grid=(2, 4),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda i, j: (i, j, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (j, 0)),
            out_shape=SDS((32, 128), jnp.float32))(x)
        fs = KL.analyze_kernel(f, SDS((2, 32, 128), jnp.float32))
        hits = [x for x in fs if x.rule == "K004"]
        assert hits and hits[0].severity == "error"
        assert "revisit" in hits[0].category

    def test_k004_contiguous_accumulation_allowed(self):
        # same revisit pattern but contiguous in grid order (the layernorm
        # dg/db and paged-decode scratch idiom): NOT a race
        def k(x_ref, o_ref):
            o_ref[...] += x_ref[0]

        f = lambda x: pl.pallas_call(
            k, grid=(2, 4),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda i, j: (i, j, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
            out_shape=SDS((16, 128), jnp.float32))(x)
        assert KL.analyze_kernel(f, SDS((2, 32, 128), jnp.float32)) == []

    def test_rules_filter(self):
        f = lambda x: pl.pallas_call(
            _copy_kernel, grid=(16,),
            in_specs=[pl.BlockSpec((8, 128), lambda j: (j, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda j: (j % 8, 0)),
            out_shape=SDS((64, 128), jnp.float32))(x)
        args = (SDS((64, 128), jnp.float32),)
        assert _rules(KL.analyze_kernel(f, *args, rules=("K001",))) == []
        assert set(_rules(KL.analyze_kernel(f, *args,
                                            rules=("K003",)))) == {"K003"}


# ---------------------------------------------------------------------------
class TestRegistryContract:
    """K005: every pallas module registers an entry with a live XLA
    fallback and an existing parity test."""

    def test_unregistered_pallas_module_flagged(self, tmp_path):
        (tmp_path / "rogue_kernel.py").write_text(
            "from jax.experimental import pallas as pl\n"
            "def f(x):\n"
            "    return pl.pallas_call(lambda i, o: None, grid=(1,))(x)\n")
        fs = KL.check_registry(search_dir=str(tmp_path), entries={})
        hits = [x for x in fs if x.category == "unregistered"]
        assert len(hits) == 1 and "rogue_kernel.py" in hits[0].where

    def test_non_pallas_module_not_flagged(self, tmp_path):
        (tmp_path / "helpers.py").write_text("def f():\n    return 1\n")
        assert KL.check_registry(search_dir=str(tmp_path),
                                 entries={}) == []

    def test_dead_fallback_flagged(self, tmp_path):
        @registry.register_kernel(
            "tmp_dead_fallback",
            fallback="paddle_tpu.no.such.module:missing",
            parity="tests/test_pallas_kernels.py::test_supports_gating",
            engine_shapes=None)
        def k(x):
            return x

        try:
            e = registry.kernel_registry()["tmp_dead_fallback"]
            fs = KL.check_registry(search_dir=str(tmp_path),
                                   entries={"tmp_dead_fallback": e})
            hits = [x for x in fs if x.category == "fallback"]
            assert hits and "not resolvable" in hits[0].message
        finally:
            registry.unregister("tmp_dead_fallback")

    def test_missing_parity_test_flagged(self, tmp_path):
        @registry.register_kernel(
            "tmp_no_parity",
            fallback="paddle_tpu.nn.functional:layer_norm",
            parity="tests/test_pallas_kernels.py::test_does_not_exist",
            engine_shapes=None)
        def k(x):
            return x

        try:
            e = registry.kernel_registry()["tmp_no_parity"]
            fs = KL.check_registry(search_dir=str(tmp_path),
                                   entries={"tmp_no_parity": e})
            hits = [x for x in fs if x.category == "parity"]
            assert hits and "test_does_not_exist" in hits[0].message
        finally:
            registry.unregister("tmp_no_parity")

    def test_undeclared_parity_flagged(self, tmp_path):
        @registry.register_kernel(
            "tmp_blank_parity",
            fallback="paddle_tpu.nn.functional:layer_norm",
            parity="",
            engine_shapes=None)
        def k(x):
            return x

        try:
            e = registry.kernel_registry()["tmp_blank_parity"]
            fs = KL.check_registry(search_dir=str(tmp_path),
                                   entries={"tmp_blank_parity": e})
            assert any(x.category == "parity" for x in fs)
        finally:
            registry.unregister("tmp_blank_parity")

    def test_shipped_registry_contract_clean(self):
        assert KL.check_registry() == []

    def test_registry_covers_all_shipped_kernels(self):
        entries = registry.load_all()
        assert {"flash_attention", "decode_attention",
                "paged_ragged_attention",
                "layernorm"} <= set(entries)
        for e in entries.values():
            assert callable(registry.resolve_fallback(e))


# ---------------------------------------------------------------------------
class TestCleanSweeps:
    """Zero findings on the kernels we actually ship, at the engine's
    real launch shapes."""

    def test_registry_sweep_zero_findings_tp1(self):
        fs = KL.lint_registry(_make_engine())
        assert fs == [], [f.format() for f in fs]

    def test_registry_sweep_zero_findings_tp2(self):
        assert len(jax.devices()) >= 2
        fs = KL.lint_registry(_make_engine(tp=2))
        assert fs == [], [f.format() for f in fs]

    def test_registry_sweep_zero_findings_speculative(self):
        # speculative adds the verify (bb, kb) paged-decode launches
        fs = KL.lint_registry(_make_engine(speculative=2))
        assert fs == [], [f.format() for f in fs]

    def test_registry_sweep_zero_findings_quant_tp1(self):
        # int8 serving swaps in the quant ragged family: int8 page
        # blocks plus the (1, 1, bs) scale blocks must all pass
        # K001-K004 at the engine's real launch shapes
        fs = KL.lint_registry(_make_engine(quantize="int8"))
        assert fs == [], [f.format() for f in fs]

    def test_registry_sweep_zero_findings_quant_tp2(self):
        assert len(jax.devices()) >= 2
        fs = KL.lint_registry(_make_engine(tp=2, quantize="int8"))
        assert fs == [], [f.format() for f in fs]

    def test_registry_sweep_zero_findings_quant_speculative(self):
        fs = KL.lint_registry(_make_engine(speculative=2,
                                           quantize="int8"))
        assert fs == [], [f.format() for f in fs]

    def test_quant_entry_skipped_on_unquantized_engine(self):
        """The quant ragged entry yields NO cases for an engine without
        an int8 pool — the sweep must skip it, not invent shapes."""
        entries = registry.load_all()
        e = entries["paged_ragged_attention_quant"]
        assert list(e.engine_shapes(_make_engine())) == []
        assert list(e.engine_shapes(_make_engine(quantize="int8")))

    def test_sweep_leaves_executable_caches_cold(self):
        eng = _make_engine(speculative=2)
        KL.lint_registry(eng)
        assert eng._ragged._cache_size() == 0

    def test_sweep_traces_every_registered_kernel(self):
        """Coverage, not absence: restricting to a never-firing rule set
        still walks every entry's engine cases without error, and every
        shipped kernel contributes at least one case at the default
        engine config."""
        eng = _make_engine()
        qeng = _make_engine(quantize="int8")
        entries = registry.load_all()
        # profile-gated entries (the quant family) contribute on the
        # engine profile that actually launches them
        cases = {name: (list(e.engine_shapes(eng))
                        or list(e.engine_shapes(qeng)))
                 for name, e in entries.items()
                 if e.engine_shapes is not None}
        assert all(cases.values()), cases


# ---------------------------------------------------------------------------
class TestHostSyncPipeline:
    """H001's explicit-sync extension: ``jax.device_get`` /
    ``jax.block_until_ready`` are host syncs BY DEFINITION, flagged
    without taint analysis — the name-taint pass cannot see device
    state carried on ``self``, which is exactly how an accidental sync
    would hide inside the async lookahead engine's pipelined step path
    and stall the window the stager works to fill."""

    def test_seeded_untagged_sync_in_step_path_fires(self, tmp_path):
        bad = tmp_path / "engine_like.py"
        bad.write_text(
            "import jax\n"
            "class Eng:\n"
            "    def _launch_packed(self, rows):\n"
            "        out = self._ragged(rows)\n"
            "        jax.block_until_ready(out)\n"     # the bug
            "        host = jax.device_get(self.kv_cache)\n"  # and again
            "        return host\n")
        fs = A.check_host_sync([str(bad)])
        cats = [f.category for f in fs]
        assert cats.count("explicit-sync") == 2, \
            [f.format() for f in fs]

    def test_tagged_sync_is_allowlisted_per_line(self, tmp_path):
        ok = tmp_path / "engine_like.py"
        ok.write_text(
            "import jax\n"
            "class Eng:\n"
            "    def warmup(self):\n"
            "        jax.block_until_ready(self.kv_cache)"
            "  # noqa: H001 (warmup timing)\n"
            "        jax.device_get(self.kv_cache)\n")       # still a bug
        fs = A.check_host_sync([str(ok)])
        assert [f.category for f in fs] == ["explicit-sync"]
        assert fs[0].where.endswith(":5")

    def test_serving_tree_is_clean_and_rule_is_live(self):
        """The shipped ops + inference/llm trees carry no untagged
        explicit sync — and the rule is NOT vacuous: the engine's
        known-legitimate sync sites (warmup timing, page migration)
        are seen and annotated, with the one blocking pull inside the
        pipelined step path tagged as the single intended sync."""
        assert A.check_host_sync() == []
        sites = [s for s in A.collect_host_sync_sites()
                 if s.category == "explicit-sync"]
        assert sites and all(s.allowed for s in sites)
        assert any(s.path.endswith("engine.py") for s in sites)


# ---------------------------------------------------------------------------
class TestSupportsConsistency:
    """``supports()`` is the caller-facing gate; the verifier is the
    proof.  The gate must never admit a shape the proof rejects with an
    ERROR (K002 >50% warnings are advisory headroom, not rejection)."""

    @staticmethod
    def _no_errors(fs, ctx):
        errs = [f.format() for f in fs if f.severity == "error"]
        assert errs == [], (ctx, errs)

    def test_flash_attention_sweep(self):
        from paddle_tpu.ops.pallas.attention_kernel import (
            flash_attention_pallas, supports)

        for seq in (128, 192, 256, 1024, 2048):
            for h in (32, 64, 128):
                if not supports(seq, seq, h):
                    continue
                x = SDS((1, seq, 2, h), jnp.float32)
                fs = KL.analyze_kernel(
                    lambda q, k, v: flash_attention_pallas(
                        q, k, v, is_causal=True), x, x, x)
                self._no_errors(fs, f"flash seq={seq} h={h}")

    @pytest.mark.parametrize("q_heads,kv_heads,head,head_v,window", [
        (32, 32, 192, 128, None), (48, 8, 128, 128, None),
        (72, 8, 128, 128, 512)])
    def test_flash_backward_at_the_cells_shapes(self, q_heads, kv_heads,
                                                head, head_v, window):
        """The ONE backward kernel at seq 8192 bf16 as kanana's and
        laguna's cells call it: the q head's dQ block is revisited on
        purpose, one k block after the other, and (grouped) the kv head's
        whole-sequence dK and dV over the whole group: K004 takes both;
        K002 holds the residency to the allowance the call reckons from
        its blocks, which is past the profile's 16 MiB."""
        from paddle_tpu.ops.pallas.attention_kernel import (
            flash_attention_pallas)

        q = SDS((1, 8192, q_heads, head), jnp.bfloat16)
        k = SDS((1, 8192, kv_heads, head), jnp.bfloat16)
        v = SDS((1, 8192, kv_heads, head_v), jnp.bfloat16)

        def vjp(q, k, v):
            def loss(*a):
                return jnp.sum(flash_attention_pallas(
                    *a, is_causal=True, window=window).astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        self._no_errors(KL.analyze_kernel(vjp, q, k, v), "flash vjp")
        bwd, = [ki for ki in KL.introspect_kernels(vjp, q, k, v)
                if "_bwd_" in ki.name]
        assert bwd.name.endswith("_bwd_dq_dkv")
        assert bwd.grid == (kv_heads, q_heads // kv_heads, 16)
        resident = KL.estimate_residency(
            [(b.block_shape, b.dtype) for b in bwd.blocks], bwd.scratch)
        assert 16 << 20 < resident < bwd.vmem_limit <= 64 << 20

    def test_decode_attention_sweep(self):
        from paddle_tpu.ops.pallas.decode_attention_kernel import (
            decode_attention_pallas, supports)

        for s_max in (64, 128, 512):
            for d in (16, 64, 128):
                if not supports(s_max, d, 4, 2):
                    continue
                fs = KL.analyze_kernel(
                    decode_attention_pallas,
                    SDS((3, 4, d), jnp.float32),
                    SDS((3, s_max, 2, d), jnp.float32),
                    SDS((3, s_max, 2, d), jnp.float32),
                    SDS((3,), jnp.int32))
                self._no_errors(fs, f"decode s_max={s_max} d={d}")

    def test_paged_ragged_sweep(self):
        from paddle_tpu.ops.pallas.ragged_attention_kernel import (
            paged_ragged_attention_pallas, supports)

        for bs in (8, 16, 32):
            for d in (16, 128):
                t = 16
                if not supports(bs, d, 4, 2, t):
                    continue
                nb, pages = 8, 4
                fs = KL.analyze_kernel(
                    paged_ragged_attention_pallas,
                    SDS((t, 4, d), jnp.float32),
                    SDS((nb, 2, bs, d), jnp.float32),
                    SDS((nb, 2, bs, d), jnp.float32),
                    SDS((4, pages), jnp.int32),
                    SDS((4,), jnp.int32),
                    SDS((4,), jnp.int32),
                    SDS((4,), jnp.int32),
                    scalar_bounds={0: (0, nb - 1), 1: (0, t), 2: (0, t),
                                   3: (0, pages * bs - 1)})
                self._no_errors(fs, f"ragged bs={bs} d={d}")

    def test_layernorm_sweep(self):
        from paddle_tpu.ops.pallas.layernorm_kernel import (
            layernorm_pallas, supports)

        for rows in (8, 64, 512):
            for c in (128, 256):
                if not supports(rows, c):
                    continue
                fs = KL.analyze_kernel(
                    layernorm_pallas,
                    SDS((rows, c), jnp.float32),
                    SDS((c,), jnp.float32),
                    SDS((c,), jnp.float32))
                self._no_errors(fs, f"ln rows={rows} c={c}")


# ---------------------------------------------------------------------------
class TestResidencyModel:
    def test_estimate_residency_double_buffers_blocks(self):
        blocks = [((8, 128), jnp.float32)]
        # one 4 KiB block, double-buffered
        assert KL.estimate_residency(blocks) == 2 * 8 * 128 * 4

    def test_scratch_counted_once(self):
        blocks = [((8, 128), jnp.float32)]
        scratch = [((8, 128), jnp.float32)]
        assert (KL.estimate_residency(blocks, scratch)
                == 3 * 8 * 128 * 4)

    def test_dtype_widths(self):
        b16 = KL.estimate_residency([((8, 128), jnp.bfloat16)])
        f32 = KL.estimate_residency([((8, 128), jnp.float32)])
        assert f32 == 2 * b16

    def test_unknown_profile_raises(self):
        with pytest.raises(KeyError):
            KL.vmem_fits([((8, 128), jnp.float32)], profile="gpu-x9")


# ---------------------------------------------------------------------------
class TestKernelLintCLI:
    """tier-1 CI gate: `graph-lint kernels --strict` must exit clean at
    the shipped engine shapes."""

    def test_cli_kernels_strict_clean_tp1(self, capsys):
        rc = A.main(["kernels", "--strict"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_kernels_strict_clean_tp2(self, capsys):
        assert len(jax.devices()) >= 2
        rc = A.main(["kernels", "--tp", "2", "--strict", "--spec", "2"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_kernels_strict_clean_quant_tp1(self, capsys):
        rc = A.main(["kernels", "--strict", "--quantize", "int8"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_kernels_strict_clean_quant_tp2_spec(self, capsys):
        assert len(jax.devices()) >= 2
        rc = A.main(["kernels", "--tp", "2", "--strict", "--spec", "2",
                     "--quantize", "int8"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_kernels_json(self, capsys):
        import json

        rc = A.main(["kernels", "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and doc["errors"] == 0
        assert doc["findings"] == []

    def test_cli_kernels_rules_filter(self, capsys):
        rc = A.main(["--rules", "K005", "kernels"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s)" in out


# ---------------------------------------------------------------------------
def test_bench_lint_artifact_embeds_kernel_sweep(tmp_path):
    """benchmarks/bench_serving.py --lint embeds the kernel verifier's
    verdict next to the cost census: a bench artifact that claims a
    throughput number also proves the kernels it ran were launchable."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_lint.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--requests", "2", "--max-new", "4", "--max-batch", "2",
         "--no-baseline", "--lint", "--artifact", artifact],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    with open(artifact) as f:
        art = json.load(f)
    kl = art["census"]["kernel_lint"]
    assert kl["clean"] is True
    assert kl["findings"] == []
    assert "kernels" in rc.stderr  # stderr summary mentions the sweep
    # the concurrency lint's verdict rides in the same artifact: the
    # host loop the bench just measured holds its lock/epoch discipline
    th = art["census"]["threads"]
    assert th["clean"] is True
    assert [f for f in th["findings"]
            if f["severity"] == "error"] == []
    assert "threads" in rc.stderr
