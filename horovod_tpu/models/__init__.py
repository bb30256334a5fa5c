"""The models the benchmarks and examples train, in flax (the reference
ships none: its examples import torchvision / Keras applications, SURVEY
§2.8): six decoder-only LMs (``transformer``, ``laguna``, ``kimi_linear``,
``olmo_hybrid``, ``sdar``, ``smallthinker``) and the image models of the
reference's benchmark protocol (``resnet``, ``vgg``, ``inception``,
``mnist``).

Imports point one way and no decoder imports another (tests/
test_models_layout.py), ``a <- b`` reading "b imports a":

    scopes <- parts <- head, experts;  delta imports none of them
    scopes, head                        <- transformer
    scopes, head, parts, experts        <- laguna, sdar, smallthinker
    scopes, head, parts, experts, delta <- kimi_linear
    scopes, head, parts, delta          <- olmo_hybrid
"""

from .experts import ExpertLayer
from .head import lm_head_loss, lm_loss
from .inception import InceptionV3
from .kimi_linear import KimiLinearLM
from .laguna import LagunaLM
from .mnist import MnistCNN
from .olmo_hybrid import OlmoHybridLM
from .resnet import ResNet, ResNet50, ResNet101
from .sdar import SdarMoeLM
from .smallthinker import SmallThinkerLM
from .transformer import TransformerLM
from .vgg import VGG16, VGG19

__all__ = ["MnistCNN", "ResNet", "ResNet50", "ResNet101",
           "TransformerLM", "lm_loss", "lm_head_loss", "VGG16", "VGG19",
           "InceptionV3",
           "LagunaLM", "ExpertLayer", "KimiLinearLM", "OlmoHybridLM",
           "SdarMoeLM", "SmallThinkerLM"]
