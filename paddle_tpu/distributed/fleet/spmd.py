"""SPMD context: the active mesh + sharding helpers.

The bridge between fleet topology (HybridCommunicateGroup.mesh) and pjit:
``use_mesh`` installs the mesh; ``param_sharding(layer)`` derives a
NamedSharding pytree from Parameter.mesh_axes metadata (set by mpu layers /
shard_parameter); ``shard_batch`` shards inputs over the data axes.
This replaces the reference's Partitioner/Resharder comm insertion
(auto_parallel/static/partitioner.py:40, reshard.py:1010) — GSPMD derives the
communication from these annotations.
"""

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class _SpmdState(threading.local):
    def __init__(self):
        self.mesh = None


_state = _SpmdState()


def current_mesh():
    return _state.mesh


@contextlib.contextmanager
def use_mesh(mesh):
    prev = _state.mesh
    _state.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh = prev


def named_sharding(mesh, axes, ndim=None):
    """axes: tuple like ("mp", None) aligned to leading dims."""
    if axes is None:
        return NamedSharding(mesh, P())
    spec = list(axes)
    if ndim is not None:
        spec = spec + [None] * (ndim - len(spec))
    # drop axis names not present in this mesh (e.g. mp metadata on a dp mesh)
    spec = [a if (a is None or a in mesh.axis_names or
                  isinstance(a, tuple)) else None for a in spec]
    return NamedSharding(mesh, P(*spec))


def param_shardings(layer, mesh):
    """dict name -> NamedSharding from Parameter.mesh_axes (default replicated,
    ZeRO-style sharding added by fleet.sharding utilities)."""
    out = {}
    for name, p in layer.state_dict().items():
        axes = getattr(p, "mesh_axes", None)
        out[name] = named_sharding(mesh, axes, ndim=len(p.shape))
    return out


def shard_parameters(layer, mesh, placement=True):
    """Physically place every parameter/buffer per its metadata."""
    sd = layer.state_dict()
    for name, p in sd.items():
        sh = named_sharding(mesh, getattr(p, "mesh_axes", None),
                            ndim=len(p.shape))
        p._data = jax.device_put(p._data, sh)
    return layer


def data_axes(mesh):
    """The mesh axes a training batch is split over: ``dp`` and a
    dedicated ``sharding`` axis (the sharding group is extra data
    parallelism, reference group_sharded), those larger than 1."""
    return tuple(a for a in ("dp", "sharding") if mesh.shape.get(a, 1) > 1)


def batch_spec(mesh, extra_batch_axes=("dp",)):
    axes = tuple(a for a in extra_batch_axes if a in mesh.axis_names)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def shard_batch(batch, mesh, axes=("dp",)):
    """device_put inputs with batch dim sharded over the data axes."""
    spec = batch_spec(mesh, axes)
    sh = NamedSharding(mesh, spec)

    def put(x):
        from ...core.tensor import Tensor
        data = x._data if isinstance(x, Tensor) else x
        out = jax.device_put(data, sh)
        return Tensor(out) if isinstance(x, Tensor) else out

    return jax.tree_util.tree_map(put, batch,
                                  is_leaf=lambda x: hasattr(x, "_data"))
