"""What the expert layer's grouped matmuls need, from shapes: the bytes
and FLOPs behind `expert_matmul_roofline.*`. One layer's experts are
three matrices each: gate and up [hidden, expert_hidden] and down
[expert_hidden, hidden]; a row is one (token, choice) pair. Counts are of
the algorithm: each touched expert's weights once, each row in once and
out once; the intermediate [rows, expert_hidden] products stay on chip in
a fused kernel and do not count."""

from __future__ import annotations


def expert_weight_bytes(hidden: int, expert_hidden: int, itemsize: int) -> float:
    """Bytes of ONE expert's three matrices."""
    return 3.0 * hidden * expert_hidden * itemsize


def expert_matmul_bytes(
    rows: int, experts_touched: int, hidden: int, expert_hidden: int, itemsize: int
) -> float:
    """Bytes the grouped matmuls of expert layers must move: the weights
    of the experts TOUCHED (summed over layers, as the program's counter
    gives them: an expert with no row is never read), and each row
    [hidden] in and out."""
    weights = experts_touched * expert_weight_bytes(hidden, expert_hidden, itemsize)
    return weights + 2.0 * rows * hidden * itemsize


def expert_matmul_flops(rows: int, hidden: int, expert_hidden: int) -> float:
    """FLOPs of the same: three [1, hidden] x [hidden, expert_hidden]
    sized products a row, 2 m k n each."""
    return 3.0 * 2.0 * rows * hidden * expert_hidden
