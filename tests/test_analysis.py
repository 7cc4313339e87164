"""Static-analysis suite (framework/analysis.py + tools/graph_lint.py).

Two halves, both load-bearing:

- seeded-bug battery: one intentionally broken sample per rule
  (un-consumed donated arg, collective on a mesh axis the declared mesh
  lacks, f64 leak, dead eqn / dead program op, host sync in an op
  kernel) — each rule MUST fire on its violation;
- clean runs: the LLM engine's full warmup executable grid at tp=1 and
  tp=2 (virtual devices) and an exported zoo program must produce ZERO
  findings — the suite is only deployable in CI if the true-positive
  rate comes with no false positives on the shipped graphs.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import analysis as A

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


def _raw_op(type_, ins, outs, attrs=()):
    from paddle_tpu.static.program_import import OpDef

    return OpDef({
        "type": type_,
        "inputs": [{"parameter": k, "arguments": list(v)}
                   for k, v in ins.items()],
        "outputs": [{"parameter": k, "arguments": list(v)}
                    for k, v in outs.items()],
        "attrs": list(attrs),
    })


# ---------------------------------------------------------------------------
class TestSeededBugs:
    """Each rule fires on its intentional violation."""

    def test_d001_unconsumed_donated_arg(self):
        f = jax.jit(lambda buf, x: x + 1.0, donate_argnums=(0,))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # jax warns on its own
            fs = A.analyze_jitted(f, SDS((64,), jnp.float32),
                                  SDS((64,), jnp.float32))
        hits = [x for x in fs if x.rule == "D001"]
        assert len(hits) == 1 and hits[0].severity == "error"
        assert "never consumed" in hits[0].message

    def test_d001_clean_when_consumed(self):
        f = jax.jit(lambda buf, x: buf.at[0].set(x[0]),
                    donate_argnums=(0,))
        fs = A.analyze_jitted(f, SDS((8,), jnp.float32),
                              SDS((8,), jnp.float32))
        assert [x for x in fs if x.rule == "D001"] == []

    def test_s001_shard_map_axis_not_on_declared_mesh(self):
        from jax.sharding import Mesh, PartitionSpec as P

        devs = jax.devices()
        assert len(devs) >= 2               # conftest forces 8 virtual
        mesh_dp = Mesh(np.array(devs[:2]), ("dp",))
        mesh_mp = Mesh(np.array(devs[:2]), ("mp",))
        f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "dp"),
                                  mesh=mesh_dp, in_specs=P("dp"),
                                  out_specs=P()))
        # the graph reduces over 'dp' but the serving mesh declares 'mp'
        fs = A.analyze_jitted(f, SDS((8,), jnp.float32), mesh=mesh_mp)
        hits = [x for x in fs if x.rule == "S001"]
        assert hits and "'dp'" in hits[0].message
        # analyzed against its OWN mesh the same graph is clean
        clean = A.analyze_jitted(f, SDS((8,), jnp.float32), mesh=mesh_dp)
        assert [x for x in clean if x.rule == "S001"] == []

    def test_s001_misplaced_array(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        mesh_a = Mesh(np.array(devs[:2]), ("mp",))
        mesh_b = Mesh(np.array(devs[2:4]), ("mp",))
        x = jax.device_put(jnp.zeros((4, 4)),
                           NamedSharding(mesh_b, P("mp", None)))
        fs = A.check_placements({"w": x}, mesh_a)
        assert fs and fs[0].rule == "S001"

    def test_t001_f64_leak(self):
        with jax.enable_x64(True):
            f = jax.jit(lambda x: x * np.float64(0.5))
            fs = A.analyze_jitted(f, SDS((4,), jnp.float64))
        hits = [x for x in fs if x.rule == "T001" and
                x.severity == "error"]
        assert hits and "float64" in hits[0].message

    def test_t001_weak_typed_output(self):
        f = jax.jit(lambda x: (x, 1.0 + 2.0))  # bare scalar flows out
        fs = A.analyze_jitted(f, SDS((4,), jnp.float32))
        hits = [x for x in fs if x.rule == "T001"]
        assert hits and hits[0].severity == "warning"
        assert "weak" in hits[0].message

    def test_g001_dead_eqn(self):
        def f(x, y):
            _ = x * 2.0                      # dead: result never used
            return y + 1.0

        fs = A.analyze_jitted(jax.jit(f), SDS((4,), jnp.float32),
                              SDS((4,), jnp.float32))
        hits = [x for x in fs if x.rule == "G001"]
        assert len(hits) == 1 and "mul" in hits[0].where

    def test_g001_dead_chain_not_just_tail(self):
        def f(x):
            a = x + 1.0
            _ = a * 3.0                      # kills the whole chain
            return x

        fs = A.analyze_jitted(jax.jit(f), SDS((4,), jnp.float32))
        assert len([x for x in fs if x.rule == "G001"]) == 2

    def test_g001_dead_op_in_program(self):
        from paddle_tpu.static.program_import import InferenceProgram

        ops = [
            _raw_op("feed", {"X": ["feed"]}, {"Out": ["x"]}),
            _raw_op("relu", {"X": ["x"]}, {"Out": ["y"]}),
            # dead: output never reaches the fetch target
            _raw_op("relu", {"X": ["y"]}, {"Out": ["orphan"]}),
            _raw_op("fetch", {"X": ["y"]}, {"Out": ["fetch"]}),
        ]
        prog = InferenceProgram(ops, {}, {})
        fs = A.analyze_program(prog)
        assert len(fs) == 1
        assert "orphan" in fs[0].message and fs[0].rule == "G001"

    def test_h001_host_sync_fires_and_allowlists(self, tmp_path):
        bad = tmp_path / "bad_op.py"
        bad.write_text(
            "import numpy as np\n"
            "def my_op(x, axis=0):\n"
            "    n = x.shape[0]          # metadata: exempt\n"
            "    host = np.asarray(x)    # device->host sync\n"
            "    return float(host.sum())\n")
        fs = A.check_host_sync([str(bad)])
        cats = sorted(f.category for f in fs)
        assert cats == ["np-asarray", "py-cast"]
        # the inline tag allowlists a site without silencing the file
        ok = tmp_path / "tagged_op.py"
        ok.write_text(
            "import numpy as np\n"
            "def my_op(x, axis=0):\n"
            "    host = np.asarray(x)  # noqa: H001 (eager by design)\n"
            "    return float(host.sum())\n")
        fs2 = A.check_host_sync([str(ok)])
        assert [f.category for f in fs2] == ["py-cast"]


# ---------------------------------------------------------------------------
class TestCleanRuns:
    """Zero false positives on the graphs we actually ship."""

    def test_ops_tree_is_h001_clean(self):
        assert A.check_host_sync() == []

    def test_engine_grid_zero_findings_tp1(self):
        fs = A.analyze_engine(_make_engine())
        assert fs == [], [f.format() for f in fs]

    def test_engine_grid_zero_findings_tp2(self):
        assert len(jax.devices()) >= 2
        fs = A.analyze_engine(_make_engine(tp=2))
        assert fs == [], [f.format() for f in fs]

    def test_engine_grid_zero_findings_speculative(self):
        """speculative=K adds the ("verify", (bb, kb)) executable family
        to the grid — the lint sweep must cover it and find nothing
        (donation consumed, shardings declared, no dtype leaks)."""
        fs = A.analyze_engine(_make_engine(speculative=2))
        assert fs == [], [f.format() for f in fs]

    def test_analysis_leaves_executable_caches_cold(self):
        """The sweep uses the AOT trace path: linting an engine must
        not compile (or retrace into) any serving executable."""
        eng = _make_engine(speculative=2)
        A.analyze_engine(eng)
        assert eng._ragged._cache_size() == 0

    def test_compile_watcher_names_weak_typed_key(self, compile_watcher):
        """A bare python scalar handed to a jitted fn builds a
        weak-typed executable; the watcher's report must carry the
        weak_type=True bit so the leak is attributable from the
        error alone."""
        f = jax.jit(lambda x, s: x * s)
        f(jnp.ones(3), jnp.asarray(2, jnp.int32))   # strong-typed warm
        with pytest.raises(A.RecompileError) as ei:
            with compile_watcher(f, labels=("f",)):
                f(jnp.ones(3), 2)            # python-scalar leak
        msg = str(ei.value)
        assert "New cache keys" in msg
        assert "weak_type=True" in msg


# ---------------------------------------------------------------------------
class TestProgramVerifier:
    def test_unknown_ops_reported_in_one_error(self):
        from paddle_tpu.static.program_import import InferenceProgram

        ops = [
            _raw_op("feed", {"X": ["feed"]}, {"Out": ["x"]}),
            _raw_op("exotic_a", {"X": ["x"]}, {"Out": ["u", "u2"]}),
            _raw_op("exotic_b", {"X": ["u"]}, {"Out": ["v"]}),
            _raw_op("fetch", {"X": ["v"]}, {"Out": ["fetch"]}),
        ]
        with pytest.raises(NotImplementedError) as ei:
            InferenceProgram(ops, {}, {})
        msg = str(ei.value)
        # BOTH gaps in one pass, with the output var names
        assert "exotic_a" in msg and "exotic_b" in msg
        assert "u, u2" in msg and msg.startswith("2 ProgramDesc")


# ---------------------------------------------------------------------------
class TestGraphLintCLI:
    """tier-1 CI gate: the full suite over the engine executable grid
    and one exported zoo program must exit clean."""

    def test_cli_engine_grid_clean(self, capsys):
        rc = A.main(["engine", "--tp", "2", "--layers", "2"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_engine_spec_grid_clean(self, capsys):
        rc = A.main(["engine", "--tp", "2", "--layers", "2",
                     "--spec", "2"])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_exported_zoo_program_clean(self, tmp_path, capsys):
        from paddle_tpu import nn
        from paddle_tpu.static import InputSpec
        from paddle_tpu.static.program_export import (
            export_reference_inference_model)

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                              nn.Linear(8, 3), nn.Softmax())
        model.eval()
        prefix = str(tmp_path / "mlp")
        export_reference_inference_model(prefix, [InputSpec([None, 4])],
                                         model)
        rc = A.main(["program", prefix])
        out = capsys.readouterr().out
        assert rc == 0 and "0 error(s), 0 warning(s)" in out

    def test_cli_fn_reports_errors_nonzero_exit(self, capsys):
        rc = A.main(["fn", "tests.test_analysis:_donating_identity",
                     "--arg", "f32[8]", "--donate", "0"])
        assert rc == 0          # donated AND returned: aliasable, clean
        rc = A.main(["fn", "tests.test_analysis:_donation_waster",
                     "--arg", "f32[8]", "--arg", "f32[8]",
                     "--donate", "0"])
        out = capsys.readouterr().out
        assert rc == 1 and "D001" in out

    def test_cli_json_output(self, capsys):
        import json

        rc = A.main(["fn", "tests.test_analysis:_donation_waster",
                     "--arg", "f32[8]", "--arg", "f32[8]",
                     "--donate", "0", "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1 and doc["errors"] == 1
        assert doc["findings"][0]["rule"] == "D001"
        assert {"severity", "where", "message"} <= set(doc["findings"][0])

    def test_cli_strict_promotes_warnings(self, capsys):
        """T001 weak-type is warning severity: exit 0 normally, exit 1
        under --strict (the documented CI hard-gate mode)."""
        argv = ["fn", "tests.test_analysis:_weak_output",
                "--arg", "f32[4]"]
        assert A.main(argv) == 0
        rc = A.main(argv + ["--strict"])
        out = capsys.readouterr().out
        assert rc == 1 and "T001" in out

    def test_cli_cost_census_json(self, capsys):
        """`graph-lint cost --json` emits the census document (entries,
        memory model, roofline) merged into the findings doc."""
        import json

        rc = A.main(["cost", "--layers", "2", "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and doc["errors"] == 0
        cen = doc["census"]
        assert cen["compile_count"] == 2
        assert cen["memory"]["weights_bytes"] > 0
        assert all("roofline" in e for e in cen["entries"])

    def test_cli_cost_m001_exit_code(self, capsys):
        rc = A.main(["cost", "--layers", "2",
                     "--memory-budget", "64KiB"])
        out = capsys.readouterr().out
        assert rc == 1 and "M001" in out

    def test_h001_default_sweep_covers_llm_tree(self):
        """The default H001 sweep now includes inference/llm: the
        scheduler/BlockManager pragmas and the engine's tagged host
        pulls must classify every site as allowlisted (zero findings
        via test_ops_tree_is_h001_clean), and the collector must
        actually SEE the llm tree — coverage, not absence."""
        sites = A.collect_host_sync_sites()
        llm = [s for s in sites
               if "inference" in s.path and s.path.endswith(".py")]
        assert llm, "H001 sweep lost the inference/llm tree"
        assert all(s.allowed for s in llm)


# CLI `fn` targets (module-level so importlib can find them)
def _donating_identity(buf):
    return buf * 1.0


def _donation_waster(buf, x):
    return x + 1.0


def _weak_output(x):
    return x, 1.0 + 2.0
