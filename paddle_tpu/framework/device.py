"""Device/place management.

The reference models devices as ``phi::Place`` + a DeviceContextPool
(paddle/fluid/platform/device_context.h).  Here a "place" is a thin label over
JAX's device list; actual placement happens through shardings and
``jax.device_put``.  ``CUDAPlace`` is accepted for API compatibility and maps to
the default accelerator.

Also the three helpers every entry point that runs on the chip starts
with (``chip_smoke.py``, ``bench.py``, ``benchmarks/bench_*.py``):
:func:`require_tpu`, :func:`describe_devices`, :func:`enable_compile_cache`;
and :func:`refuse_chip_sharing`, which everything that starts local
worker processes calls first.
"""

import os

import jax


class Place:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(Place):
    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    pass


class CUDAPlace(Place):
    """Accepted for source compatibility; maps to the default accelerator."""


_current_device = None


def _default_device_str():
    backend = jax.default_backend()
    if backend == "tpu":
        return "tpu:0"
    if backend == "gpu":
        return "gpu:0"
    return "cpu"


def get_device():
    return _current_device or _default_device_str()


def set_device(device):
    """Accepts "cpu", "tpu", "tpu:0", "gpu:0" etc.  Returns the place."""
    global _current_device
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name == "cpu":
        place = CPUPlace()
    elif name in ("tpu", "xpu"):
        place = TPUPlace(idx)
    elif name in ("gpu", "cuda"):
        place = CUDAPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    _current_device = device
    return place


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return jax.default_backend() == "tpu"


def require_tpu():
    """``jax.devices()[0]`` if it is a TPU, else RuntimeError naming what
    JAX found.  Entry points that prove or measure something about the
    chip call this first; none of them has a CPU mode."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found platform {dev.platform!r} "
            f"(device_kind {dev.device_kind!r}); this entry point runs on "
            f"a TPU only and has no CPU mode")
    return dev


def describe_devices():
    """The device as JAX reports it — printed with every result."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of the cache key: a
    directory that moves (tempdir, pid, time) never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def refuse_chip_sharing(n_children, starter):
    """Raise unless ``starter`` may start ``n_children`` local processes
    that will use JAX.

    A TPU chip belongs to one process at a time: children of a parent
    that holds it, and siblings that would share it, fail or hang at
    start-up.  Children pinned to the CPU (``JAX_PLATFORMS=cpu``) are
    always fine, and so is any number of them once this process has
    resolved its own backend to the CPU.  Otherwise one child of a
    parent that has not touched JAX is the only safe shape — one
    process per host drives every local chip.
    """
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.default_backend() != "tpu":
            return
        raise RuntimeError(
            f"{starter}: this process holds the TPU, and a chip belongs "
            f"to one process at a time — a child that needs it fails or "
            f"hangs.  Start workers before touching JAX, or pin them to "
            f"the CPU with JAX_PLATFORMS=cpu.")
    if n_children > 1:
        raise RuntimeError(
            f"{starter}: {n_children} local processes would share this "
            f"host's TPU chips, and a chip belongs to one process at a "
            f"time.  Run ONE process per host (it drives every local "
            f"chip), or set JAX_PLATFORMS=cpu for a CPU run.")
