from distributed_ml_pytorch_tpu.models.cnn import LeNet, AlexNet, get_model
from distributed_ml_pytorch_tpu.models.resnet import ResNet, get_resnet
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
from distributed_ml_pytorch_tpu.models.hybrid import HybridLM
from distributed_ml_pytorch_tpu.models.generate import generate, generate_tp

__all__ = [
    "LeNet", "AlexNet", "ResNet", "TransformerLM", "HybridLM", "get_model",
    "get_resnet", "generate", "generate_tp",
]
