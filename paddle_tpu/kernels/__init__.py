"""Pallas TPU kernels — the fused-op layer (reference's CUDA kernel zoo:
flash_attn, fused_rope, fused_bias_dropout_residual_ln,
fused_multi_transformer, MoE dispatch).

Each kernel module exposes the op with a jnp reference implementation and a
Pallas TPU kernel. On the ``tpu`` backend the kernels are compiled by Mosaic;
on the ``cpu`` backend (the test suite) they run in Pallas interpret mode and
``flash_attention.attention`` takes the jnp path. Any other backend raises
(``pallas_flash._interpret_mode``, ``flash_attention._use_pallas``).
"""
from . import flash_attention
