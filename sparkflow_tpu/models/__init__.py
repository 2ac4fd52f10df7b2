"""Model zoo: registry models usable anywhere a graph-DSL model is.

Two model spec formats travel through the framework as JSON strings (the wire
format the Estimator's ``tensorflowGraph`` Param carries):

1. graph-DSL specs (``sparkflow-tpu-graph``) built by ``build_graph`` — arbitrary
   user models, executed by :class:`sparkflow_tpu.graphdef.GraphModel`;
2. registry specs (``sparkflow-tpu-model``) naming a model family + config —
   the zoo below, hand-written functional JAX with TPU sharding rules
   (tensor-parallel PartitionSpecs, ring/flash attention).

``model_from_json`` dispatches on the format marker; everything downstream
(Trainer, predict_func, model_loader) is format-agnostic.

Families: ``mlp``, ``cnn``, ``autoencoder`` (graph-DSL preset builders mirroring
the reference examples), ``transformer_classifier`` / ``transformer_lm`` (BERT
-class encoder, flash/ring attention, TP/SP shardings), ``resnet50`` (CIFAR/
ImageNet residual network, stateless norm), ``rnn_classifier`` / ``rnn_lm``
(LSTM/GRU via lax.scan, fused gate matmuls), ``sparse_moe_lm`` (RMSNorm,
rotary, grouped query heads, a learned top-k key selection and dropless
SiLU-gated experts of which a share may be held; training path only),
``block_diffusion_lm`` (the same decoder trained by block diffusion: a clean
and a noised copy of every row under one block-structured attention mask, a
masked-token loss; training path only), ``looped_lm`` (one stack of
sandwich-norm blocks run several times with the same weights, a head and an
exit gate after every pass, the expected loss under the exit distribution;
training path only).
"""

from .registry import model_from_json, register_model, build_registry_spec
from . import presets
from .transformer import TransformerClassifier, TransformerLM
from .moe import MoETransformerLM
from .sparse_moe_lm import SparseMoELM
from .block_diffusion_lm import BlockDiffusionLM, noise_rows
from .looped_lm import LoopedLM
from .resnet import ResNet
from .rnn import RNNClassifier, RNNLM

__all__ = [
    "model_from_json", "register_model", "build_registry_spec", "presets",
    "TransformerClassifier", "TransformerLM", "MoETransformerLM",
    "SparseMoELM", "BlockDiffusionLM", "noise_rows", "LoopedLM", "ResNet",
    "RNNClassifier", "RNNLM",
]
