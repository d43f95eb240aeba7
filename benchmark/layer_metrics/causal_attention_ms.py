"""``attention_ms`` under the name the causal cell declares it by:
device time per step, chip 0, in the ``fused_multihead_attention`` op
and its gradient.  One reader (``attention_ms.py``); a second entry
because the manifest pairs this one with ``causal_flash_roofline``,
which counts the causal half of the square where ``flash_roofline``
counts all of it."""

from benchmark.layer_metrics.attention_ms import (  # noqa: F401
    LAYER, MOVES, UNIT, belongs, read)
