"""Tensor-parallel (mpu) layers.

Redesign of reference mp_layers
(python/paddle/distributed/fleet/layers/mpu/mp_layers.py:35,173,343,524).
The reference embeds explicit collectives (_c_identity/_mp_allreduce) into
forward/backward; here layers are **ordinary dense math carrying sharding
metadata** (``Parameter.mesh_axes``): under pjit, GSPMD partitions the matmul
over the 'mp' mesh axis and inserts the identical collectives itself —
column-parallel keeps activations sharded on the feature dim, row-parallel
emits the all-reduce after the partial matmul.  Inside an explicit shard_map
region the layers fall back to hand-written lax collectives, matching the
reference semantics op-for-op.

The layers TAG their results (``fleet.recompute.tagged``,
:data:`SAVED_BY_NAME`): a matmul's product, and behind a row-parallel one the
``mp`` all-reduce GSPMD put there, are dear to make again, so a rematerialised
block keeps them by name (``remat=True`` of either trainer, or a list that
holds the names).  A step that keeps none of them holds no tag.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ....core.tensor import Tensor
from ....nn import functional as F
from ....nn.initializer import XavierUniform, Normal
from ....nn.layer_base import Layer
from ....ops.registry import op
from ..recompute import PROJECTIONS as SAVED_BY_NAME, tagged


def _in_shard_map(axis):
    """True when tracing inside a shard_map that binds ``axis``."""
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


class ColumnParallelLinear(Layer):
    """W sharded on the output (column) dim over 'mp'
    (reference mp_layers.py:173)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=XavierUniform())
        self.weight.mesh_axes = (None, "mp")
        if has_bias:
            self.bias = self.create_parameter((out_features,), attr=None,
                                              is_bias=True)
            self.bias.mesh_axes = ("mp",)
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if not self.gather_output:
            out = _shard_hint(out, ("mp",), dim=-1)
        return tagged(out, SAVED_BY_NAME[0])


class RowParallelLinear(Layer):
    """W sharded on the input (row) dim over 'mp'; partial results all-reduce
    (reference mp_layers.py:343)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=XavierUniform())
        self.weight.mesh_axes = ("mp", None)
        if has_bias:
            self.bias = self.create_parameter((out_features,), attr=None,
                                              is_bias=True)
            self.bias.mesh_axes = (None,)
        else:
            self.bias = None

    def forward(self, x):
        return tagged(F.linear(x, self.weight, self.bias), SAVED_BY_NAME[1])


class VocabParallelEmbedding(Layer):
    """Embedding table sharded on the vocab dim (reference mp_layers.py:35)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=Normal(0.0, 0.02))
        self.weight.mesh_axes = ("mp", None)

    def forward(self, x):
        return F.embedding(x, self.weight)


class ParallelCrossEntropy(Layer):
    """Vocab-sharded softmax CE (reference mp_layers.py:524 →
    c_softmax_with_cross_entropy op).  Under GSPMD the plain CE over sharded
    logits lowers to the same pattern (local max/sum + mp all-reduce)."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return F.cross_entropy(input, label, reduction="none",
                               ignore_index=self.ignore_index)


@op()
def _shard_hint_op(x, axes, dim):
    # annotate-only op: identity in eager, sharding hint when a mesh is active
    from ..spmd import current_mesh
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = current_mesh()
    if mesh is not None and isinstance(x, jax.core.Tracer):
        # the other dims are left to GSPMD: ``None`` there says "NOT
        # sharded", and gathered every column-parallel output (and its
        # gradient) over ``dp`` (PERF.md section 6, PR 29)
        spec = [PartitionSpec.UNCONSTRAINED] * x.ndim
        spec[dim] = axes[0]
        try:
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, PartitionSpec(*spec)))
        except Exception:
            return x
    return x


def _shard_hint(x, axes, dim=-1):
    return _shard_hint_op(x, axes, dim)
