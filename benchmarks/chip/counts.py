"""Operation and byte counts behind the roofline and utilization metrics.

Every count is the work the algorithm needs, worked out from shapes:
what a faster kernel may not skip.  Recomputation, padding and the
integer work of regenerating directions are not counted.

Round close (``x <- x + sum_n w_n r_n v_n(seed_n)``, one scalar per
client): each of the C clients' d direction elements is multiplied by
its scalar and added into the update, 2 operations per element.  The
bytes are the parameters read and written once, plus each client's
seed (uint32) and k float32 scalars.
"""
from __future__ import annotations

import json
import os

__all__ = ["close_flops", "close_bytes", "load_peaks",
           "CLOSE_VPU_OPS_PER_ELEMENT"]

HERE = os.path.dirname(os.path.abspath(__file__))

# Integer and select operations the fused close spends per regenerated
# element on the rademacher chain (one SplitMix32 round after the
# per-row state is hoisted: add, 2 xor-shift pairs, 2 multiplies, the
# final xor-shift, the xor with the column, the sign-bit extract, the
# select, and the scalar multiply-add).  No peak for this work is
# published, so it is recorded beside the metric and not divided by one.
CLOSE_VPU_OPS_PER_ELEMENT = 15


def close_flops(d: int, cohort: int, k: int = 1) -> int:
    """Floating-point operations of one round close: 2 per client element."""
    return 2 * int(cohort) * int(k) * int(d)


def close_bytes(d: int, cohort: int, param_bytes: int, k: int = 1) -> int:
    """HBM bytes of one round close: params in and out, seeds, scalars."""
    return 2 * int(d) * int(param_bytes) + int(cohort) * (4 + 4 * int(k))


def load_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` from ``peaks.json``.

    A device that the table does not list is an error, never a default.
    """
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"peaks.json lists {sorted(table['devices'])}")
    return dict(table["devices"][device_kind], source=table["source"])
