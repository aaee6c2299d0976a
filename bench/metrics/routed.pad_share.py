"""routed.pad_share: percent of the window's coded block volume that is
padding.  The volume is ``Σ m³`` over the blocks the session handed the
backend (``run.blocks``, counted by plan shape); the work in it is
``Σ n_e·K·F`` of the completed routed requests' products
(``readers.routed_macs``).  The tiling pads each expert's ``[n_e, K]×[K,
F]`` product up to whole ``m×m`` blocks, so ragged row counts show here."""
from bench.harness.readers import routed_macs


def read(run):
    volume = sum(count * key[-1] ** 3 for key, count in run.blocks.items())
    work = routed_macs(run)
    if not volume or not work:
        return None
    return 100.0 * (1.0 - work / volume)
