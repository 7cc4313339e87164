"""Custom-op extension path: register_custom_op / register_pallas_op /
cpp_extension.load / host_op_from_extension, plus the op-schema single
source.

Reference parity targets: paddle/fluid/framework/custom_operator.cc
(runtime op registration), python/paddle/utils/cpp_extension/ (JIT C++
build), paddle/phi/api/yaml/ops.yaml (single-source signatures).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops import OPS, registry
from paddle_tpu.utils import cpp_extension, register_custom_op


def _unique(name):
    i = 0
    while f"{name}{i}" in OPS:
        i += 1
    return f"{name}{i}"


class TestRegisterCustomOp:
    def test_forward_only_uses_jax_vjp(self):
        import jax.numpy as jnp

        name = _unique("cube_op")
        cube = register_custom_op(name, lambda x: x * x * x)
        x = paddle.to_tensor(np.array([2.0], np.float32), stop_gradient=False)
        y = cube(x)
        np.testing.assert_allclose(y.numpy(), [8.0])
        y.backward()
        np.testing.assert_allclose(x.grad.numpy(), [12.0])  # 3x^2
        assert name in OPS and "custom" in OPS[name].tags

    def test_custom_backward_overrides(self):
        name = _unique("scale2")
        # deliberately wrong-by-2 backward proves the override is used
        op = register_custom_op(
            name,
            lambda x: 2.0 * x,
            backward=lambda gout, x: 10.0 * gout)
        x = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        y = op(x).sum()
        y.backward()
        np.testing.assert_allclose(x.grad.numpy(), 10.0 * np.ones(3))

    def test_none_grad_becomes_zero(self):
        name = _unique("axpy")
        op = register_custom_op(
            name,
            lambda x, y: x + y,
            backward=lambda gout, x, y: (gout, None))
        x = paddle.to_tensor(np.ones(2, np.float32), stop_gradient=False)
        y = paddle.to_tensor(np.ones(2, np.float32), stop_gradient=False)
        op(x, y).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.ones(2))
        np.testing.assert_allclose(y.grad.numpy(), np.zeros(2))

    def test_duplicate_name_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_custom_op("matmul", lambda x: x)

    def test_works_under_jit(self):
        from paddle_tpu.jit import to_static

        name = _unique("jit_custom")
        op = register_custom_op(name, lambda x: x * 5.0)

        @to_static
        def f(x):
            return op(x) + 1.0

        x = paddle.to_tensor(np.ones(4, np.float32))
        np.testing.assert_allclose(f(x).numpy(), 6.0 * np.ones(4))


class TestCppExtension:
    SRC = """
    extern "C" {
    void saxpy(const float* x, const float* y, float* out, long long n,
               float a) {
      for (long long i = 0; i < n; ++i) out[i] = a * x[i] + y[i];
    }
    long long checksum(const long long* v, long long n) {
      long long s = 0;
      for (long long i = 0; i < n; ++i) s += v[i];
      return s;
    }
    }
    """

    def test_load_inline_source_and_call(self):
        import ctypes

        mod = cpp_extension.load(
            "test_ext", [self.SRC],
            functions={
                "saxpy": ("void", ["float*", "float*", "float*", "int64",
                                   "float"]),
                "checksum": ("int64", ["int64*", "int64"]),
            })
        x = np.arange(5, dtype=np.float32)
        y = np.ones(5, dtype=np.float32)
        out = np.empty(5, dtype=np.float32)
        fp = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        mod.saxpy(fp(x), fp(y), fp(out), 5, 2.0)
        np.testing.assert_allclose(out, 2 * x + y)

        v = np.arange(10, dtype=np.int64)
        assert mod.checksum(
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), 10) == 45

    def test_build_is_cached(self):
        m1 = cpp_extension.load("cache_ext", [self.SRC])
        m2 = cpp_extension.load("cache_ext", [self.SRC])
        assert m1._so_path == m2._so_path

    def test_host_op_from_extension(self):
        import jax

        name = _unique("host_relu")

        def host_fn(x):
            return np.maximum(x, 0.0)

        op = cpp_extension.host_op_from_extension(
            name, host_fn,
            out_shape_fn=lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            backward=lambda gout, x: gout * (x > 0))
        x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32),
                             stop_gradient=False)
        y = op(x)
        np.testing.assert_allclose(y.numpy(), [0.0, 2.0])
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), [0.0, 1.0])

        # host callback must also work under jit
        from paddle_tpu.jit import to_static

        @to_static
        def f(t):
            return op(t) * 2.0

        np.testing.assert_allclose(f(x).numpy(), [0.0, 4.0])


class TestOpSchema:
    def test_schema_loaded_and_canonical(self):
        from paddle_tpu.ops.schema import OP_SCHEMA

        assert len(OP_SCHEMA) >= 389
        m = registry.schema("matmul")
        assert [a[1] for a in m["args"]] == ["x", "y", "transpose_x",
                                            "transpose_y"]
        assert m["backward"] == "matmul_grad"
        assert registry.schema("sparse.matmul")["group"] == "sparse_ops"

    def test_schema_covers_inventory(self):
        from paddle_tpu.ops.inventory import OP_INVENTORY
        from paddle_tpu.ops.schema import OP_SCHEMA

        missing = [n for n in OP_INVENTORY if n not in OP_SCHEMA]
        assert not missing, missing[:10]

    def test_wrong_signature_rejected_at_registration(self):
        """The schema is load-bearing: registering an op under a schema'd
        name with a contradicting signature must fail (the reference's
        yaml/api_gen single-source role)."""
        from paddle_tpu.ops import registry
        from paddle_tpu.ops.registry import OpSchemaError

        saved = registry.OPS.pop("matmul")
        try:
            with pytest.raises(OpSchemaError, match="missing required"):
                @registry.op("matmul")
                def bad_matmul(a, b):  # schema says (x, y, ...)
                    return a @ b
        finally:
            registry.OPS["matmul"] = saved

    def test_every_registered_op_validates_or_is_documented(self):
        """Sweep: all import-time registrations pass _validate_schema (a
        mismatch would have raised at import, but assert explicitly so the
        property is pinned) and every divergence entry names a real op."""
        from paddle_tpu.ops import registry
        from paddle_tpu.ops.schema import OP_SCHEMA
        from paddle_tpu.ops.schema_compat import SCHEMA_DIVERGENCES

        for name, od in registry.OPS.items():
            if od.jax_fn is not None:
                registry._validate_schema(name, od.jax_fn)  # must not raise
        unknown = [n for n in SCHEMA_DIVERGENCES if n not in OP_SCHEMA]
        assert not unknown, unknown

    def test_schema_defaults_autofill(self):
        """A schema default fills in for an impl param left default-less."""
        from paddle_tpu.ops import registry

        name = _unique("schema_fill")
        # fabricate a schema entry with a defaulted arg the impl leaves bare
        from paddle_tpu.ops.schema import OP_SCHEMA
        OP_SCHEMA[name] = {
            "group": "ops",
            "args": [("Tensor", "x", False, None),
                     ("float", "alpha", True, 2.5)],
            "outputs": [("Tensor", "out")], "backward": None,
            "inplace": None}
        try:
            @registry.op(name)
            def f(x, alpha):  # no python default: schema supplies 2.5
                return x * alpha

            out = f(paddle.to_tensor(np.array([2.0], np.float32)))
            np.testing.assert_allclose(out.numpy(), [5.0])
            out = f(paddle.to_tensor(np.array([2.0], np.float32)), alpha=1.0)
            np.testing.assert_allclose(out.numpy(), [2.0])
        finally:
            del OP_SCHEMA[name]
            registry.OPS.pop(name, None)
