"""Pallas TPU kernels for hot ops.

The XLA lowerings in `nn/layers/*` are the default accelerated path (the
reference's cuDNN-helper seam, SURVEY.md §2.3); this package holds hand-tiled
Pallas kernels for the cases where a custom schedule beats XLA's — the TPU
analog of the reference shipping cuDNN-specific kernels next to the generic
path. Kernels run in interpret mode on CPU (tests) and compile via Mosaic on
TPU.
"""
from .expert_gmm import expert_gmm
from .flash_attention import (flash_attention, flash_decode,
                              flash_decode_append,
                              flash_decode_paged, kv_append)
from .kda_step import kda_step
from .mla_decode import latent_append, mla_decode
from .mla_prefill import mla_prefill
from .ssm_step import ssm_step

__all__ = ["expert_gmm", "flash_attention", "flash_decode",
           "flash_decode_append",
           "flash_decode_paged", "kda_step", "kv_append", "latent_append",
           "mla_decode", "mla_prefill", "ssm_step"]
