"""The least time a chip could take for a step's operations and bytes,
from the published peaks. Kept with the benchmark so that no PR that
claims a gain can change the yardstick. No JAX.

The operations and bytes themselves are counted from a configuration's
shapes by its architecture entry
(benchmark/architectures/<name>.py::decode_step, ::prefill).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

def load_peaks(bench_dir: str, device_kind: str) -> Dict[str, float]:
    """The published peaks of `device_kind`; an unknown device is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                   f"(known: {sorted(table)}); add its row to peaks.json")


def least_seconds(work: Dict[str, float], peaks: Dict[str, float],
                  matmul_peak: str = "bf16_tflops") -> Dict[str, Any]:
    """The larger of operations over peak and bytes over peak, and which
    of the two bounds. int8 weights are multiplied in bf16 after a
    convert, so the bf16 peak is the one that applies."""
    t_ops = work["flops"] / (peaks[matmul_peak] * 1e12)
    t_mem = work["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
