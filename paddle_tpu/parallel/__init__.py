"""paddle_tpu.parallel: SPMD parallelism building blocks.

- pipeline.spmd_pipeline — in-program pipeline parallelism (shard_map +
  ppermute + scan over schedule ticks)
- trainer.SpmdTrainStep — the hybrid dp×pp×mp(×sharding)(+sp) train step
- trainer.compiled_collectives — what GSPMD put into a compiled step
"""

from .pipeline import spmd_pipeline  # noqa: F401
from .trainer import SpmdTrainStep, compiled_collectives  # noqa: F401
