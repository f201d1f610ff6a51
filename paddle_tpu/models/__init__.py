"""Model zoo: the reference's benchmark/demo model families, rebuilt on the
paddle_tpu layer API.

Reference configs: /root/reference/benchmark/paddle/image/{alexnet,googlenet,
resnet,vgg,smallnet_mnist_cifar}.py and /root/reference/v1_api_demo/mnist
(LeNet). The RNN/LSTM families land with the sequence machinery.

All builders take a data Variable and append ops to the default (or given)
program; they return the logits variable. ``data_format`` defaults to NHWC —
the TPU-native layout (channels-last maps directly onto the MXU's lane
dimension) — whereas the reference hardcodes NCHW for cuDNN.
"""
from .lenet import lenet5
from .alexnet import alexnet
from .vgg import vgg
from .resnet import resnet_imagenet, resnet_cifar10
from .googlenet import googlenet
from .mobilenet import mobilenet
from .smallnet import smallnet_mnist_cifar
from .seq2seq import shared_nmt_params, transformer_nmt_teacher
from .transformer import (lm_parameters, transformer_lm,
                          transformer_lm_beam_search,
                          transformer_lm_generate)
from .wide_deep import wide_deep, wide_deep_loss

__all__ = [
    "lm_parameters",
    "transformer_lm", "transformer_lm_beam_search", "transformer_lm_generate",
    "wide_deep", "wide_deep_loss",
    "shared_nmt_params", "transformer_nmt_teacher",
    "lenet5", "alexnet", "vgg", "resnet_imagenet", "resnet_cifar10",
    "googlenet", "mobilenet", "smallnet_mnist_cifar",
]
