"""Runner ``spmd_train``: ``parallel.SpmdTrainStep`` on the mesh the
configuration names (``"mesh": {"dp": 2, "mp": 2}``; ``"trainer"`` holds
further ``SpmdTrainStep`` arguments such as ``remat``) over the host's
chips.  Everything but the step object is ``runners/train.py``'s."""

from . import train


class Program(train.Program):
    def __init__(self, ctx):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from paddle_tpu.distributed.fleet.topology import build_mesh
        from paddle_tpu.parallel import SpmdTrainStep

        mesh_cfg = ctx.config["mesh"]
        self.chips = 1
        for n in mesh_cfg.values():
            self.chips *= int(n)
        devices = jax.devices()[:self.chips]
        self.mesh = build_mesh(devices=devices, **mesh_cfg)
        self._flat = Mesh(np.array(devices), ("x",))
        self.ref = ctx.reference()
        self.model = train.build_model(ctx)
        # the trainer stacks and shards its own copy and keeps the model
        # (``trainer.model``); whole on the first chip, the model's
        # leaves, their stacked copy, that chip's shard and its moments
        # came to 16.0 of its 16.9 GB.  Spread over the chips, no chip
        # ever holds a whole copy
        for p in self.model.parameters():
            p._data = self._spread(p._data)
        self.step = SpmdTrainStep(self.model,
                                  train.optimizer_for(ctx, self.model),
                                  self.mesh, **ctx.config.get("trainer", {}))

    def _spread(self, a):
        """``a`` split over the chips along its largest axis that
        divides (placement only; whole where none does)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = [i for i, d in enumerate(a.shape) if d % self.chips == 0]
        spec = [None] * a.ndim
        if axes:
            spec[max(axes, key=lambda i: a.shape[i])] = "x"
        return jax.device_put(a, NamedSharding(self._flat, P(*spec)))

    def put(self, ids):
        return ids              # the trainer shards the batch itself

    def __call__(self, ids, labels):
        return self.step.step(ids, labels)

    def free(self):
        import jax

        for p in self.model.parameters():
            if not p._data.is_deleted():
                p._data.delete()
        super().free()

    def state(self):
        import jax

        sd = self.step.state_dict()
        moments = jax.tree_util.tree_map(
            lambda s: s["moment1"], sd["opt_state"],
            is_leaf=lambda s: isinstance(s, dict) and "moment1" in s)
        return self.ref.keyed(sd["params"]), self.ref.keyed(moments)

    def reference_shard(self):
        """The reference is plain ``jax.numpy`` all the same: its
        float32 tree (22 GB for 8 layers) is only PLACED over the four
        chips, largest axis split, and the batch rows likewise."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spread, flat = self._spread, self._flat
        return {"params": lambda t: jax.tree_util.tree_map(spread, t),
                "batch": lambda a: jax.device_put(
                    a, NamedSharding(flat, P("x")))}


def run(ctx):
    return train.run(ctx, program_cls=Program)
