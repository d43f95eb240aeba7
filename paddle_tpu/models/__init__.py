"""Model zoo matching BASELINE.json configs:
LeNet (MNIST), ResNet-50 (ImageNet), BERT-base, Transformer NMT,
Wide&Deep CTR, word2vec, plus GPT-2 and OLMoE decoders — all built on the fluid layers API so they run
unchanged on the reference framework.  The decoders added since
(``laguna``, ``moonlight``, ``lfm2``, ``evabyte``, ``solar_open2``,
``ouro``, ``xing4``, ``phi4flash``, ``kimi_linear``, ``sdar``,
``nemotron_h``) are imported by name where they are used, each
with its plain reference in ``models/reference/``.
"""

from . import lenet
from . import resnet
from . import se_resnext
from . import bert
from . import gpt
from . import olmoe
from . import transformer
from . import wide_deep
from . import word2vec
