"""field_gemm_roofline.routed: ``field_gemm_roofline`` in the routed-expert
cells: the least time of the window's coded blocks, of every side the
tiling chose, over the stage programs' device time, per request."""
from bench.harness.readers import roofline_share as read  # noqa: F401
