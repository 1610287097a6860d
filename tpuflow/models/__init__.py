"""Flax model zoo + losses.

Parity with the reference's model/ops layer (torch.nn MLP + CrossEntropyLoss +
SGD, reference my_ray_module.py:94-112,141-142) plus the larger models named by
the acceptance configs (ResNet-18/50, GPT-2) behind the same trainer API.
"""

from tpuflow.models.mlp import NeuralNetwork
from tpuflow.models.losses import cross_entropy_loss, accuracy

__all__ = ["NeuralNetwork", "cross_entropy_loss", "accuracy", "get_model"]


def get_model(name: str, **kwargs):
    """Model registry — models are pluggable behind the trainer API (the
    acceptance configs name ResNet-18/50 and GPT-2-medium, BASELINE.md)."""
    name = name.lower()
    if name in ("mlp", "neural_network", "fashion_mnist_mlp"):
        return NeuralNetwork(**kwargs)
    if name in ("resnet18", "resnet50"):
        from tpuflow.models.resnet import ResNet18, ResNet50

        return (ResNet18 if name == "resnet18" else ResNet50)(**kwargs)
    if name in ("gpt2", "gpt2_medium", "gpt2-medium"):
        from tpuflow.models.gpt2 import GPT2, GPT2Config

        if name != "gpt2":
            kwargs.setdefault("config", GPT2Config.medium())
        return GPT2(**kwargs)
    if name in ("vit", "vit_tiny", "vit_small"):
        from tpuflow.models.vit import ViT

        if name == "vit_tiny":  # ViT-Ti/16
            for k, v in dict(
                n_embd=192, n_layer=12, n_head=3, patch_size=16
            ).items():
                kwargs.setdefault(k, v)
        elif name == "vit_small":  # ViT-S/16
            for k, v in dict(
                n_embd=384, n_layer=12, n_head=6, patch_size=16
            ).items():
                kwargs.setdefault(k, v)
        return ViT(**kwargs)
    if name in ("sdar", "sdar_moe"):
        from tpuflow.models.sdar import Sdar

        return Sdar(**kwargs)
    raise KeyError(
        f"unknown model {name!r}; available: mlp, resnet18, resnet50, "
        "gpt2, gpt2_medium, vit, vit_tiny, vit_small, sdar"
    )
