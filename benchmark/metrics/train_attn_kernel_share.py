"""Kernels: ``attn_kernel_share`` as read in the training cells, where the
Mosaic calls are the flash attention forward and its two backward kernels
and the share moves ``train_tokens_per_s``."""
import readers

reduce = readers.same_as("attn_kernel_share")
