from .decorator import decorate, float32_output, keep_float32
from .fp16_lists import AutoMixedPrecisionLists
