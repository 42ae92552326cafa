"""What one training step needs, whatever implements it: the bytes and
operations of the algorithm, counted from the batch's own shape.

Per distinct touched slot the step must read and write the weight row
and both FTRL rows (w, n, z: 6 * width * 4 bytes). Per occurrence it
must read the slot id, the row index and the value/mask (12 bytes).
Operations: per occurrence the forward and backward of the row math
(`flops_per_occurrence` of the model), per touched element the FTRL
update (12). A count is a lower bound, so a share of a peak built on it
cannot pass 100%.
"""

from __future__ import annotations

import numpy as np

FTRL_FLOPS_PER_ELEMENT = 12
OCCURRENCE_BYTES = 12


def flops_per_occurrence(width: int) -> int:
    """Forward: add the row into the sums and the squares (3 * width);
    backward: one multiply-add per element against the row's sums and
    its own value (4 * width)."""
    return 7 * width


def step_needs(distinct_slots: float, occurrences: float, width: int) -> dict:
    return {
        "bytes": distinct_slots * 6 * width * 4 + occurrences * OCCURRENCE_BYTES,
        "flops": occurrences * flops_per_occurrence(width)
        + distinct_slots * width * FTRL_FLOPS_PER_ELEMENT,
    }


def gather_needs(distinct_slots: float, occurrences: float, width: int) -> dict:
    """Distinct rows read once, one row written per occurrence."""
    return {"bytes": (distinct_slots + occurrences) * width * 4 + occurrences * 4, "flops": 0.0}


def scatter_ftrl_needs(distinct_slots: float, occurrences: float, width: int) -> dict:
    """One gradient row read per occurrence; w, n, z read and written
    per distinct slot."""
    return {
        "bytes": occurrences * width * 4 + occurrences * 4 + distinct_slots * 6 * width * 4,
        "flops": occurrences * width + distinct_slots * width * FTRL_FLOPS_PER_ELEMENT,
    }


def least_seconds(needs: dict, peak: dict, chips: int = 1) -> tuple[float, str]:
    """The least time `chips` chips could take, and which bound holds."""
    by_bytes = needs["bytes"] / (peak["hbm_bytes_per_s"] * chips)
    by_flops = needs["flops"] / (peak["flops_per_s"] * chips)
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")


def batch_shape(slots: np.ndarray, batch_size: int) -> dict:
    """Mean distinct slots and occurrences a step over the pass's
    batches; `slots` int [rows, F]."""
    n = slots.shape[0] // batch_size
    distinct = [np.unique(slots[i * batch_size:(i + 1) * batch_size]).size for i in range(n)]
    return {"distinct_slots": float(np.mean(distinct)), "occurrences": float(batch_size * slots.shape[1])}


def kernel_roofline_pct(run: dict, pattern: str, needs_fn) -> float | None:
    """A kernel's share of its roofline in a traced run: the least time
    one chip could take for its share of the step's needed bytes, over
    the kernel's device time a step. None where the trace has no such
    kernel."""
    from . import trace

    tr = run.get("trace")
    if not tr or not tr.get("devices") or not run.get("peak") or not run["trace_steps"]:
        return None
    seconds = trace.op_seconds(tr["ops"], pattern) / run["trace_steps"]
    if seconds <= 0:
        return None
    shape = run["shape"]()
    per_chip = 1.0 / run["chips"]
    needs = needs_fn(shape["distinct_slots"] * per_chip, shape["occurrences"] * per_chip, run["width"])
    return 100.0 * least_seconds(needs, run["peak"])[0] / seconds


def predict_needs(distinct_slots: float, occurrences: float, width: int) -> dict:
    """What one served batch needs: each distinct row read once, one row
    and 12 bytes (slot, field, mask) read an occurrence; the forward of
    the row math (3 * width an occurrence). The pCTRs written are a few
    bytes a row and are left out: a lower bound."""
    return {
        "bytes": distinct_slots * width * 4 + occurrences * (width * 4 + OCCURRENCE_BYTES),
        "flops": occurrences * 3 * width,
    }
