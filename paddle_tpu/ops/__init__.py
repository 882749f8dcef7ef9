"""paddle_tpu.ops — Pallas TPU kernels (flash attention, ring attention,
the paged KV pool's decode read and row write, MoE dispatch). The analog of
the reference's hand-written CUDA kernels in phi/kernels/{gpu,fusion};
everything else is XLA-generated."""
from . import flash_attention, ragged_attention  # noqa: F401
