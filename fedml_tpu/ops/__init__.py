"""Pallas TPU kernels for the hot ops.

The reference delegates all performance-critical math to cuDNN/torch kernels
(SURVEY §2 native-code note); the TPU-native analog is XLA fusion for almost
everything, plus hand-written Pallas kernels where blockwise algorithms beat
XLA's lowering — currently flash attention (ops/flash_attention.py), which
ops/attention.py puts under both transformer models at training lengths."""

from fedml_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_bthd,
)
