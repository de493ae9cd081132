"""Pallas TPU kernels for the hot ops: flash attention (exported here),
the selective scan (``metisfl_tpu.ops.selective_scan``, imported as a
module: its routed entry point has the module's name) and the grouped
products of a routed-expert layer (``metisfl_tpu.ops.grouped_matmul``)."""

from metisfl_tpu.ops.flash_attention import (FLASH_MIN_SEQ, attention,
                                             flash_attention,
                                             flash_block_census)

__all__ = ["flash_attention", "attention", "flash_block_census",
           "FLASH_MIN_SEQ"]
