"""``ops/pallas``: the ONE rule that places a call (``_refusal`` /
``_dispatch``) held over its nine dispatchers, the flash kernels' block
rule at the benchmark's shapes, and the direction of the package's imports.

The kernels' values are other files' business (``test_pallas_kernels.py``,
``test_ssd_scan_kernel.py``, ...): here a kernel's entry point and its XLA
composition are both stubs that say they were reached.
"""

import ast
import pathlib
import warnings

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import (attention_kernel, causal_conv_kernel,
                                   cca_mix_kernel, common,
                                   eva_attention_kernel,
                                   gated_norm_kernel, mla_expand_kernel,
                                   moe_run_sum_kernel, registry,
                                   ssd_scan_kernel)


def _x(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mla(heads, nope, rope, v_dim, seq=32):
    table = _x(seq, rope // 2, dtype=jnp.float32)
    return ((_x(1, seq, heads, nope + rope), _x(1, seq, heads, nope + v_dim),
             _x(1, seq, rope), table, table),
            {"nope": nope, "interleave": True})


def _ssd(chunk, t=256, nh=8, p=64, g=1, n=128):
    f32 = jnp.float32
    return ((_x(1, t, nh, p), _x(1, t, nh, dtype=f32), _x(nh, dtype=f32),
             _x(1, t, g, n), _x(1, t, g, n), _x(nh, dtype=f32), chunk), {})


def _run_sum(width):
    return ((_x(64, width), _x(64, dtype=jnp.int32), None), {"max_run": 8})


def _conv(channels):
    return ((_x(1, 64, channels), _x(channels, 4), _x(channels)), {})


def _norm(channels):
    return ((_x(1, 64, channels), _x(1, 64, 2 * channels), _x(channels), 2,
             1e-5), {"start": channels})


def _cca(head_dim, seq=64, n=4, kv=2):
    f32 = jnp.float32
    table = _x(seq, head_dim // 4, dtype=f32)
    return ((_x(1, seq, n, head_dim), _x(1, seq, kv, head_dim),
             _x(1, seq, kv, head_dim), _x(n * head_dim, 2),
             _x(n, 2, head_dim, head_dim), _x(kv * head_dim, 2),
             _x(kv, 2, head_dim, head_dim), _x(kv, dtype=f32), table, table,
             1e-5), {})


def _grouped(rows):
    return ((_x(rows, 64), _x(2, 64, 32), jnp.array([rows // 2, rows // 2])),
            {})


# dispatcher: (the call, where its kernels' entry point and its composition
# live, operands it serves, operands it refuses, what the refusal says, the
# name of its records, its counter in the sums)
DISPATCHERS = {
    "flash_attention": (
        pk.flash_attention,
        (attention_kernel, "flash_attention_pallas"), (pk, "_xla_attention"),
        ((_x(1, 1024, 2, 64),) * 3, {"is_causal": True}),
        ((_x(1, 1024, 2, 256),) * 3, {"is_causal": True}),
        "attention_kernel.supports() refuses", None),
    "eva_attention": (
        pk.eva_attention,
        (eva_attention_kernel, "eva_attention_pallas"),
        (pk, "_xla_eva_attention"),
        ((_x(1, 64, 2, 16),) * 3 + (_x(1, 16, 2, 16),) * 2 + (32, 4), {}),
        ((_x(1, 64, 2, 256),) * 3 + (_x(1, 16, 2, 256),) * 2 + (32, 4), {}),
        "eva_attention_kernel.supports() refuses", None),
    "ssd_scan": (
        pk.ssd_scan, (ssd_scan_kernel, "ssd_scan_pallas"),
        ("paddle_tpu.nn.functional", "_ssd_scan_rows"),
        _ssd(128), _ssd(16),
        "ssd_scan_kernel.supports() refuses", "ssd_calls"),
    "mla_expand": (
        pk.mla_expand_qkv, (mla_expand_kernel, "mla_expand_pallas"),
        (pk, "_xla_mla_expand_qkv"),
        _mla(4, 128, 64, 128), _mla(4, 32, 16, 32),
        "mla_expand_kernel.supports() refuses", "mla_expand_calls"),
    "moe_run_sum": (
        pk.moe_run_sum, (moe_run_sum_kernel, "moe_run_sum_pallas"),
        ("paddle_tpu.incubate.distributed.models.moe.dropless", "_run_sums"),
        _run_sum(128), _run_sum(96),
        "moe_run_sum_kernel.supports() refuses", "moe_run_sum_calls"),
    "causal_conv": (
        pk.causal_conv1d, (causal_conv_kernel, "causal_conv_pallas"),
        ("paddle_tpu.nn.functional", "_causal_conv1d_silu"),
        _conv(128), _conv(96),
        "causal_conv_kernel.supports() refuses", "causal_conv_calls"),
    "gated_norm": (
        pk.gated_rms_norm, (gated_norm_kernel, "gated_norm_pallas"),
        ("paddle_tpu.models.nemotron_h", "_gated_norm_composed"),
        _norm(256), _norm(192),
        "gated_norm_kernel.supports() refuses", "gated_norm_calls"),
    "cca_mix": (
        pk.cca_mix, (cca_mix_kernel, "cca_mix_pallas"),
        ("paddle_tpu.models.zaya", "_mix_composed"),
        _cca(128), _cca(64),
        "cca_mix_kernel.supports() refuses", "cca_mix_calls"),
    "grouped_matmul": (
        pk.grouped_matmul,
        ("jax.experimental.pallas.ops.tpu.megablox.ops", "gmm"),
        (jax.lax, "ragged_dot"),
        _grouped(256), _grouped(100),
        "rows not a multiple of 128", None),
}


class _Reached(list):
    """The names of the stubs that ran; ``stub(where, name)`` puts one in
    a function's place."""

    def __init__(self, monkeypatch):
        super().__init__()
        self._monkeypatch = monkeypatch

    def stub(self, where, name):
        import importlib

        if isinstance(where, str):
            where = importlib.import_module(where)
        self._monkeypatch.setattr(
            where, name, lambda *a, **k: self.append(name) or name)


@pytest.fixture
def reached(monkeypatch):
    return _Reached(monkeypatch)


@pytest.mark.parametrize("situation", ["off_the_tpu", "refused", "served"])
@pytest.mark.parametrize("name", list(DISPATCHERS))
def test_one_rule_places_every_dispatchers_call(name, situation, reached,
                                                monkeypatch):
    """Off the TPU: the composition, without a word, recorded with the
    reason ``NO_TPU``.  On it (``_use_pallas`` and the backend's name
    patched, as the chip shows them) a shape the kernels refuse: the
    composition, exactly ONE ``KernelFallbackWarning``, recorded with the
    refusal; a shape they serve: the kernels' entry point, no warning."""
    call, entry, composition, served, refused, says, counter = \
        DISPATCHERS[name]
    reached.stub(*entry)
    reached.stub(*composition)
    if situation != "off_the_tpu":
        monkeypatch.setattr(pk, "_use_pallas", lambda: True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args, kwargs = refused if situation == "refused" else served
    before = pk.traced_call_sums()
    common.traced_calls.clear()     # the log keeps its newest 1,024 only
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = call(*args, **kwargs)
    took = entry[1] if situation == "served" else composition[1]
    assert reached == [took] and got == took
    aloud = [w for w in caught
             if issubclass(w.category, pk.KernelFallbackWarning)]
    assert len(aloud) == (1 if situation == "refused" else 0), caught
    rec, = common.traced_calls
    assert rec["kernel"] == name
    if situation == "served":
        assert (rec["path"], rec["reason"]) == ("kernel", None)
    else:
        assert rec["path"] == "composition"
        assert (says if situation == "refused" else pk.NO_TPU) \
            in rec["reason"]
    if situation == "refused":
        assert name in str(aloud[0].message) \
            and says in str(aloud[0].message)
    after = pk.traced_call_sums()
    want = {} if counter is None else {
        counter: 1, f"{counter}_composed": int(situation != "served")}
    assert {k: after[k] - before[k] for k in after if after[k] != before[k]} \
        == {k: n for k, n in want.items() if n}


def test_a_short_sequence_takes_xla_by_choice_without_a_word(reached,
                                                             monkeypatch):
    """Under ``FLASH_MIN_SEQ`` the fused XLA attention is a choice, not a
    fallback: recorded, silent; the constant is the dispatcher's to read
    (``chip_smoke.py``) and a test's to patch."""
    reached.stub(attention_kernel, "flash_attention_pallas")
    reached.stub(pk, "_xla_attention")
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = _x(1, pk.FLASH_MIN_SEQ // 2, 2, 64)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pk.flash_attention(q, q, q, is_causal=True)
    assert reached == ["_xla_attention"]
    assert common.traced_calls[-1]["reason"] == pk.BY_CHOICE
    monkeypatch.setattr(pk, "FLASH_MIN_SEQ", 0)
    pk.flash_attention(q, q, q, is_causal=True)
    assert reached[-1] == "flash_attention_pallas"


# ------------------------------------------------------ the block rule --

@pytest.mark.parametrize("seq_q,seq_k,blocks,cell", [
    (2048, 2048, (512, 512), "GPT one chip, the four-chip shard"),
    (8192, 8192, (512, 512), "kanana, laguna full and window"),
    (4096, 4096, (512, 512), "the hybrid, the looped cell"),
    (16384, 16384, (512, 512), "evabyte's length, were it flash's"),
    (1024, 1024, (512, 512), "FLASH_MIN_SEQ"),
    (768, 768, (256, 256), "three blocks of 256"),
    (384, 1152, (128, 128), "q and k axes apart"),
    (192, 192, (64, 64), "no 128 divides it"),
    (200, 200, (8, 8), "none of 512 ... 64 divides it"),
    (100, 512, (None, 512), "no candidate divides q: supports() says no"),
])
def test_the_flash_block_rule(seq_q, seq_k, blocks, cell):
    """The largest of 512, 256, 128, 64 that divides each axis, else what
    ``pick_block`` finds under the default of 128: 512 x 512 at every shape
    a cell of the benchmark runs (what the tuner, now gone, returned there:
    its head candidate).  ``supports()`` refuses a length with no block."""
    assert attention_kernel._blocks(seq_q, seq_k) == blocks, cell
    by_the_letter = tuple(
        next((b for b in (512, 256, 128, 64) if seq % b == 0), None)
        or common.pick_block(seq, 128) for seq in (seq_q, seq_k))
    assert blocks == by_the_letter
    assert attention_kernel.supports(seq_q, seq_k, 128) \
        == (None not in blocks)


# ------------------------------------------------- imports point downward --

PACKAGE = pathlib.Path(pk.__file__).parent


def _package_imports(path):
    """The names ``path`` imports from its own package, wherever in the
    file: ``from . import a`` -> ``a``, ``from .b import c`` -> ``b``,
    ``from .. / from paddle_tpu.ops.pallas ...`` -> what follows."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            found |= {node.module.split(".")[0]} if node.module \
                else {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level > 1:
            found.add(f"{'.' * node.level}{node.module or ''}")
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [node.module] if isinstance(node, ast.ImportFrom) \
                else [a.name for a in node.names]
            found |= {n for n in names if n.startswith("paddle_tpu")}
    return found


@pytest.mark.parametrize("module", registry.KERNEL_MODULES + ("common",))
def test_a_kernel_file_imports_downward_only(module):
    """A kernel file takes ``common`` and ``registry`` of this package and
    nothing else of ``paddle_tpu``: not the dispatcher (``from . import
    pick_block`` was an import of ``__init__``), not a sibling kernel file.
    ``common`` takes nothing of the package at all."""
    allowed = set() if module == "common" else {"common", "registry"}
    assert _package_imports(PACKAGE / f"{module}.py") <= allowed


def test_every_kernel_file_is_held_to_it():
    on_disk = {p.stem for p in PACKAGE.glob("*_kernel.py")}
    assert on_disk == set(registry.KERNEL_MODULES)
