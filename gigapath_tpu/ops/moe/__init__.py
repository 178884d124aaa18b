from gigapath_tpu.ops.moe.routing import (  # noqa: F401
    GroupLimitedSigmoidGate,
    Top1Gate,
    Top2Gate,
    top1_gating,
    top2_gating,
    topk_softmax_gating,
)
from gigapath_tpu.ops.moe.moe_layer import DroplessMoE, MOELayer  # noqa: F401
