from .kernel import BLOCK_S
from .ops import (order_score, order_score_delta, order_score_delta_bitmask,
                  pad_for_kernel)
from .ref import order_score_ref

__all__ = ["order_score", "order_score_delta", "order_score_delta_bitmask",
           "pad_for_kernel", "order_score_ref", "BLOCK_S"]
