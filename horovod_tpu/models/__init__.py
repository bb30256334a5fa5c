"""Benchmark / example model zoo.

The reference ships no model code — its examples import torchvision / Keras
applications (SURVEY §2.8). This environment has no TPU-side model zoo, so
the models the benchmarks need (ResNet-50/101, a small MNIST convnet) are
implemented here in flax, sized and configured to match the reference
benchmark protocol (``examples/pytorch_synthetic_benchmark.py``).
"""

from .inception import InceptionV3
from .kimi_linear import KimiLinearLM
from .laguna import ExpertLayer, LagunaLM
from .mnist import MnistCNN
from .olmo_hybrid import OlmoHybridLM
from .resnet import ResNet, ResNet50, ResNet101
from .sdar import SdarMoeLM
from .transformer import TransformerLM, lm_head_loss, lm_loss
from .vgg import VGG16, VGG19

__all__ = ["MnistCNN", "ResNet", "ResNet50", "ResNet101",
           "TransformerLM", "lm_loss", "lm_head_loss", "VGG16", "VGG19",
           "InceptionV3",
           "LagunaLM", "ExpertLayer", "KimiLinearLM", "OlmoHybridLM",
           "SdarMoeLM"]
