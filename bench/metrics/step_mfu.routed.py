"""step_mfu.routed: percent of the chip's int8 peak that the routed layer's
plain work takes over the whole traced window: ``2·Σ n_e·K·F``
operations of the completed requests (``readers.routed_macs``), each
field multiply-add counted as the ``work.LIMB_MACS`` int8 multiply-adds
of one limb product, over the window's length times the peak of every
chip.  Padding, the coding's redundancy and idle time all lower it, and
no kernel leaving the path can raise it."""
from bench.harness import work
from bench.harness.readers import routed_macs


def read(run):
    macs = routed_macs(run)
    if run.trace is None or not run.peaks or not macs:
        return None
    lo, hi = run.window_ns
    ops = 2 * macs * work.LIMB_MACS
    return 100.0 * ops / ((hi - lo) / 1e9 * run.peaks["int8_ops_per_s"]
                          * run.chips)
