"""Model zoo + registry.

Reference: the reference ships no model library — users build Keras models
in notebooks (examples/: an MNIST MLP, an MNIST CNN, and a CIFAR-10 CNN in
the example workflows) and the framework carries them as serialized JSON +
weights. Here models are flax ``nn.Module``s registered by name so they can
be serialized as ``{name, kwargs}`` (see distkeras_tpu/utils/serde.py) and
rebuilt anywhere, which plays the role of Keras ``to_json``.
"""

from distkeras_tpu.models.registry import get_model, register_model, model_spec  # noqa: F401
from distkeras_tpu.models.mlp import MLP  # noqa: F401
from distkeras_tpu.models.cnn import MNISTCNN, CIFARCNN  # noqa: F401
from distkeras_tpu.models.transformer import TransformerLM  # noqa: F401
from distkeras_tpu.models.deepseek_v32 import DeepseekV32LM  # noqa: F401
from distkeras_tpu.models.mimo_v2 import MiMoV2LM  # noqa: F401
from distkeras_tpu.models.solar_open2 import SolarOpen2LM  # noqa: F401
from distkeras_tpu.models.glm4_moe_lite import Glm4MoeLiteLM  # noqa: F401
from distkeras_tpu.models.afmoe import AfmoeLM  # noqa: F401
