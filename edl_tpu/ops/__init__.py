"""Pallas TPU kernels for the framework's hot ops.

The compute path is XLA-compiled JAX; these kernels take over exactly where
XLA's automatic fusion cannot help: blockwise-online attention
(`flash_attention`), which avoids materializing the (S, S) score matrix
that the plain einsum+softmax attention pays, and the Mamba-2 SSD chunked
scan (`ssd.ssd_scan`, imported where it is used), which keeps a chunk's
decay tiles and the carried state in VMEM.
"""

from edl_tpu.ops.flash_attention import flash_attention

__all__ = ["flash_attention"]
