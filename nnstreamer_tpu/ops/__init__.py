"""TPU ops: pallas kernels + jitted primitives for stream hot paths."""

from .classify import top1, topk_indices

__all__ = ["top1", "topk_indices"]
