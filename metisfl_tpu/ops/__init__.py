"""Pallas TPU kernels for the hot ops: flash attention (exported here)
and the selective scan (``metisfl_tpu.ops.selective_scan``, imported as a
module: its routed entry point has the module's name)."""

from metisfl_tpu.ops.flash_attention import (FLASH_MIN_SEQ, attention,
                                             flash_attention)

__all__ = ["flash_attention", "attention", "FLASH_MIN_SEQ"]
