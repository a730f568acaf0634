"""Model zoo for horovod_tpu benchmarks and examples.

The reference ships per-framework example models (ResNet-50/MNIST synthetic
benchmarks, /root/reference/examples/tensorflow2_synthetic_benchmark.py,
pytorch_synthetic_benchmark.py, *_mnist.py). Here the models are flax modules
designed TPU-first: bfloat16 compute with fp32 params/accumulators, shapes
that tile onto the 128x128 MXU, and no data-dependent Python control flow.
"""

from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152  # noqa: F401
from .mlp import MLP  # noqa: F401
from .command_a_plus import CommandAPlus, CommandAPlusConfig  # noqa: F401
from .longcat_flash import LongcatFlash, LongcatFlashConfig  # noqa: F401
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig  # noqa: F401
from .transformer import (CacheSpec, PagedCache, PlaneGroup,  # noqa: F401
                          Transformer, TransformerConfig)
from .vgg import VGG, VGG11, VGG13, VGG16, VGG19  # noqa: F401
from .inception import InceptionV3  # noqa: F401
