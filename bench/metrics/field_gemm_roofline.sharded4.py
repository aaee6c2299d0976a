"""field_gemm_roofline.sharded4: ``field_gemm_roofline`` in the cells on a
mesh of chips, whose throughput is ``products_per_s.sharded4``: the least
time of the window's blocks over all the cell's chips, divided by the
stage programs' device time per product on the busiest chip."""
from bench.harness.readers import roofline_share as read  # noqa: F401
