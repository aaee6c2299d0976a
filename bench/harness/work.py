"""The work of one coded block, from the plan's ``n, s, t, z, m``.

A block ``Y = AᵀB`` with ``m×m`` operands runs four stages (the
``ProtocolStages`` programs of ``repro.mpc.planner``); each is a field
GEMM, ``[M, K] @ [K, N]`` over ``F_p``, of ``M·K·N`` field multiply-adds:

* encode: the shares of both operands, ``[n, ts+z] @ [ts+z, m²/(ts)]``
  twice;
* worker: each of the ``n`` workers' ``[m/t, m/s] @ [m/s, m/t]``;
* exchange: the G mix ``[n, n] @ [n, (m/t)²]`` and the mask term
  ``[n, z] @ [z, (m/t)²]``;
* decode: ``[t², t²+z] @ [t²+z, (m/t)²]``.

The least time of a block on a chip is the larger of two bounds:

* compute: each field multiply-add is ``LIMB_MACS`` int8 multiply-adds
  (4 byte limbs of a residue below 2³², multiplied schoolbook), two
  operations each, at the int8 peak;
* memory: every stage reads its operands and writes its result once, at
  ``RESIDUE_BYTES`` a residue, at the HBM peak.

A limb scheme with fewer than 16 int8 products per field product (a
Karatsuba split, say) would make ``LIMB_MACS`` too high; a later benchmark
change has to revisit it before such a scheme is measured.
"""
from __future__ import annotations

from typing import Dict, Tuple

LIMB_MACS = 16
RESIDUE_BYTES = 4


def stage_gemms(n: int, s: int, t: int, z: int, m: int
                ) -> Dict[str, Tuple[Tuple[int, int, int, int], ...]]:
    """``{stage: ((batch, M, K, N), ...)}``, the field GEMMs of one block."""
    mt, ms = m // t, m // s
    return {
        "encode": ((1, n, t * s + z, mt * ms), (1, n, t * s + z, ms * mt)),
        "worker": ((n, mt, ms, mt),),
        "exchange": ((1, n, n, mt * mt), (1, n, z, mt * mt)),
        "decode": ((1, t * t, t * t + z, mt * mt),),
    }


def block_field_macs(n: int, s: int, t: int, z: int, m: int) -> Dict[str, int]:
    """Field multiply-adds of one block, per stage."""
    return {stage: sum(b * mm * k * nn for b, mm, k, nn in gemms)
            for stage, gemms in stage_gemms(n, s, t, z, m).items()}


def block_residues(n: int, s: int, t: int, z: int, m: int) -> int:
    """Residues the stages read and write: each GEMM's operands and
    result once (the stage tables included), plus the two ``m×m``
    operands' split and the ``m×m`` result's reassembly."""
    total = 0
    for gemms in stage_gemms(n, s, t, z, m).values():
        for b, mm, k, nn in gemms:
            total += b * (mm * k + k * nn + mm * nn)
    return total + 3 * m * m


def block_least_time(n: int, s: int, t: int, z: int, m: int,
                     peaks: Dict[str, float], chips: int = 1
                     ) -> Tuple[float, str]:
    """``(seconds, bound)``: the least time of one block on ``chips``."""
    macs = sum(block_field_macs(n, s, t, z, m).values())
    compute = 2 * macs * LIMB_MACS / (chips * peaks["int8_ops_per_s"])
    memory = (block_residues(n, s, t, z, m) * RESIDUE_BYTES
              / (chips * peaks["hbm_bytes_per_s"]))
    return (compute, "compute") if compute >= memory else (memory, "memory")
