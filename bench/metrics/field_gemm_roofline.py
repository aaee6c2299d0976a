"""field_gemm_roofline: percent of the stage programs' device time that the
window's field GEMM work needs at the chip's peaks.

The least time of each coded block is the larger of its compute bound
(field multiply-adds of encode, worker, exchange and decode from the
plan's ``n, s, t, z, m``, 16 int8 multiply-adds each, at the int8 peak)
and its memory bound (the stages' operand and result residues at 4 bytes,
at the HBM peak): ``bench/harness/work.py``.  The share divides that, per
product, by ``stages.device_ms`` per product.
"""
from bench.harness.readers import roofline_share as read  # noqa: F401
