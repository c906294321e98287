"""CNN substrate: the paper's five benchmark networks, VGG-16 and
ConvNeXt-T in pure JAX.

Convolutions execute the ARM-CL way — im2col + GEMM — so the layer
descriptors that drive the performance model (core/descriptors.py) are the
*same* objects that parameterize the compute.
"""
from .graph import Graph, Node, major_layers
from .models import (
    MODELS, alexnet, convnext, convnext_tiny, googlenet, mobilenet, resnet50,
    squeezenet,
)

__all__ = [
    "Graph",
    "Node",
    "major_layers",
    "MODELS",
    "alexnet",
    "convnext",
    "convnext_tiny",
    "googlenet",
    "mobilenet",
    "resnet50",
    "squeezenet",
]
