from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate  # noqa: F401
from .grad_clip import ClipGradForMOEByGlobalNorm  # noqa: F401
from .moe_layer import (DroplessMoELayer, GroupedExperts,  # noqa: F401
                        MoELayer, Relu2MLP, SwiGLUMLP, TopKRouter,
                        global_gather, global_scatter)
