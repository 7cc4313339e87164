"""Gradient clipping (reference python/paddle/fluid/clip.py).

``_clip_jax(params, grads)`` is the pure form shared by the eager step and the
jit TrainStep; ClipGradByGlobalNorm under hybrid parallelism is extended in
distributed/fleet (norm allreduced across model-parallel axes).
"""

import jax
import jax.numpy as jnp


class ClipGradBase:
    def _clip_jax(self, params, grads):
        raise NotImplementedError

    def clip_pytree(self, grads):
        flat, treedef = jax.tree_util.tree_flatten(grads)
        with jax.named_scope("grad_clip"):
            clipped = self._clip_jax([None] * len(flat), flat)
        return jax.tree_util.tree_unflatten(treedef, clipped)


class ClipGradByValue(ClipGradBase):
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def _clip_jax(self, params, grads):
        return [jnp.clip(g, self.min, self.max) for g in grads]


class ClipGradByNorm(ClipGradBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def _clip_jax(self, params, grads):
        out = []
        for g in grads:
            norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            scale = jnp.minimum(self.clip_norm / jnp.maximum(norm, 1e-12), 1.0)
            out.append((g.astype(jnp.float32) * scale).astype(g.dtype))
        return out


class ClipGradByGlobalNorm(ClipGradBase):
    def __init__(self, clip_norm, group_name="default_group",
                 auto_skip_clip=False):
        self.clip_norm = float(clip_norm)
        self.group_name = group_name

    def global_norm(self, grads):
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads)
        return jnp.sqrt(sq)

    def _clip_jax(self, params, grads):
        gnorm = self.global_norm(grads)
        scale = self.clip_norm / jnp.maximum(gnorm, self.clip_norm)
        return [(g.astype(jnp.float32) * scale).astype(g.dtype) for g in grads]
