"""The work each relational kernel has to do, counted from the operation's
logical shapes (rows, columns read, dtype width) and never from tiles or
the grid, and the table of device peaks it is held against.

A kernel that does the same work another way is judged against the same
count, so its share of the roofline compares across implementations.
"""
from __future__ import annotations

import dataclasses

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

INT32_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       "add its published numbers to PEAKS") from None


@dataclasses.dataclass(frozen=True)
class Work:
    kernel: str
    ops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """The least time the chip could take: operations over peak
        operation rate or bytes over HBM bandwidth, whichever is larger."""
        return max(self.ops / peak["flops"], self.bytes / peak["hbm_bytes_per_s"])


def filter_count(rows: int, columns: int) -> Work:
    """COUNT of a conjunction of inclusive ranges: every predicate column
    is read once (int32) and compared against two bounds."""
    return Work("filter_count", 2.0 * rows * columns,
                float(rows * columns * INT32_BYTES))


def merge_join_count(left_rows: int, right_rows: int) -> Work:
    """Cardinality of an equi-join of two sorted int32 key columns: a merge
    reads each key once and compares it once."""
    n = left_rows + right_rows
    return Work("merge_join_count", float(n), float(n * INT32_BYTES))


def plan_work(physical, rows: dict) -> list[Work]:
    """The kernel work of one executed physical plan; ``rows`` maps each
    dataset to its live rows. A ``KernelRangeCount`` is a ``filter_count``
    over its predicate columns, a kernel ``JoinCountOp`` a
    ``merge_join_count`` of the datasets its two inputs scan. Nodes are
    recognised by name, so a plan that no longer has them yields no work."""
    out = []
    stack = [physical] if physical is not None else []
    while stack:
        node = stack.pop()
        kind = type(node).__name__
        if kind == "KernelRangeCount":
            out.append(filter_count(rows[node.dataset], len(node.cols)))
        elif kind == "JoinCountOp" and getattr(node, "kernel", False):
            left, right = node.children
            out.append(merge_join_count(_scanned_rows(left, rows),
                                        _scanned_rows(right, rows)))
        stack.extend(getattr(node, "children", ()) or ())
    return out


def _scanned_rows(node, rows: dict) -> int:
    """The rows of the first dataset that ``node``'s subtree scans."""
    stack = [node]
    while stack:
        n = stack.pop()
        if getattr(n, "dataset", None) in rows:
            return rows[n.dataset]
        stack.extend(getattr(n, "children", ()) or ())
    raise ValueError(f"no scanned dataset under {type(node).__name__}")
