"""Roofline arithmetic shared by the cost functions of every family."""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: bytes per element of the dtypes a configuration may state
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, Any]
                  ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")
