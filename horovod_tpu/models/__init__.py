"""Model zoo for benchmarks and examples.

Mirrors the reference's benchmark surface (SURVEY.md §6): ResNet-50/101/152
(tf_cnn_benchmarks / synthetic benchmark models), an MNIST-scale MLP/CNN
(keras mnist examples), and transformer families (BERT-large / GPT-2) for the
BASELINE.json north-star configs.

Five further models are plain functions over a dict of arrays, each a
module imported by name (``from horovod_tpu.models import afmoe``) and not
from here:
``sdar_moe`` (SDAR-MoE trained by block diffusion), ``afmoe`` (Trinity-Mini's
block: window and full attention in one stack, a gated attention output, a
sigmoid router with a shared expert, leading dense layers),
``joyai_flash`` (JoyAI-LLM-Flash: latent attention with one rotary key
shared by all heads, and a multi-token-prediction module on the shared
embedding and head), ``kimi_linear`` (Kimi-Linear: Kimi Delta Attention,
a gated delta rule with a decay for every channel through the chunked scan
kernels of ``parallel/kda.py``, beside latent attention without positions)
and ``lfm2_moe`` (LFM2-8B-A1B: a gated short convolution as the token mixer
of most layers, through ``parallel/gated_conv.py``, beside grouped-query
attention); the benchmark's jobs (``benchmarks/jobs/``) train all five.
They build from one module of shared parts, ``decoder`` (the norm, the
embedding, the loop over runs of stacked layers, the dense and
shared-expert halves, the chunked head and the next-token loss), and none
imports another.
"""

from .resnet import (  # noqa: F401
    ResNet, ResNet50, ResNet101, ResNet152, create_resnet50,
)

from .transformer import (  # noqa: F401
    Transformer, TransformerConfig, create_gpt2, create_bert, lm_loss,
    stack_block_params, unstack_block_params,
    GPT2_SMALL, GPT2_MEDIUM, GPT2_LARGE, BERT_BASE, BERT_LARGE,
)

from .mlp import MLP, MnistCNN, create_mlp  # noqa: F401
