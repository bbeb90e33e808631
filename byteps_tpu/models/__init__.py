"""Model zoo for byteps_tpu benchmarks and examples.

The reference has no in-tree model code (models come from example/ scripts
and external hubs); this zoo provides the four BASELINE.json benchmark
vehicles natively: MLP/MNIST (config 1), ResNet-50 (config 2), BERT-large
(config 3), Llama-3 (config 4).
"""

from . import (bert, lfm2, llama, mellum, mlp, moe, resnet, sdar,  # noqa: F401
               vgg)
