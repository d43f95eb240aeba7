from .decorator import decorate, keep_float32
from .fp16_lists import AutoMixedPrecisionLists
