"""Flax model zoo.

TPU-native counterparts of the reference's example model zoo
(reference examples/keras/models/*.py, examples/pytorch/models/mlp.py):
small federated workloads (MLP, CNNs, LSTM) plus the scale-ladder models
from BASELINE.md (ResNet-20, ViT, BERT, Llama+LoRA) and the attention /
state-space hybrid JambaLite (Mamba mixer + MQA attention, tied head), the
latent-attention decoders MlaMoeLite and ScMoeLite, DsaMoeLite
(learned sparse attention over GQA beside routed experts) and KdaMoeLite
(Kimi Delta Attention and sparse latent attention under four-stream mHC).
"""

from metisfl_tpu.models.zoo.mlp import MLP, HousingMLP
from metisfl_tpu.models.zoo.cnn import BrainAge3DCNN, FashionMnistCNN, Cifar10CNN
from metisfl_tpu.models.zoo.resnet import ResNet20
from metisfl_tpu.models.zoo.rnn import LSTMClassifier
from metisfl_tpu.models.zoo.transformer import (
    TRANSFORMER_RULES,
    BertLite,
    DeltaMixer,
    DsaMoeLite,
    ExpertShareMLP,
    HyperConnection,
    JambaLite,
    KdaMoeLite,
    LatentAttention,
    LlamaLite,
    LoRADense,
    MambaMixer,
    MlaMoeLite,
    MoEMLP,
    ScMoeLite,
    ShortcutMoEBlock,
    SparseIndexer,
    ViTLite,
)

__all__ = [
    "MLP", "HousingMLP", "FashionMnistCNN", "Cifar10CNN", "ResNet20",
    "BrainAge3DCNN", "LSTMClassifier",
    "ViTLite", "BertLite", "LlamaLite", "JambaLite", "MambaMixer",
    "MlaMoeLite", "LatentAttention", "ExpertShareMLP",
    "ScMoeLite", "ShortcutMoEBlock", "DsaMoeLite", "SparseIndexer",
    "KdaMoeLite", "DeltaMixer", "HyperConnection",
    "LoRADense", "MoEMLP",
    "TRANSFORMER_RULES",
]
