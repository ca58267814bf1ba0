"""What the gated delta-rule layers (Kimi Delta Attention) need, from
shapes: the bytes and FLOPs behind `kda_state_roofline` and
`kda_scan_roofline`. A head keeps a state of [d, d] floats (d the head
size, keys and values alike) and each of the three convolution streams
the last `kernel - 1` inputs. Counts are of the algorithm, in float32:
each array once in and once out, nothing for what a fused program keeps
on chip."""

from __future__ import annotations

ITEMSIZE = 4  # the state, the tails and the recurrence's inputs are float32


def state_step_bytes(rows: int, heads: int, head_dim: int, kernel: int) -> float:
    """A decode step's traffic for `rows` (live slot, layer) rows: the
    state [heads, d, d] in and out, the convolutions' tails [kernel - 1,
    3 heads d] in and out, and the token's q, k, v, g rows [heads, d] with
    its beta [heads] in and its output [heads, d] back."""
    state = heads * head_dim * head_dim
    tails = (kernel - 1) * 3 * heads * head_dim
    token = 5 * heads * head_dim + heads
    return ITEMSIZE * float(rows) * (2 * state + 2 * tails + token)


def state_step_flops(rows: int, heads: int, head_dim: int) -> float:
    """The same rows' arithmetic: the decay (1 a state element), k^T S
    (2), the rank-one correction (2) and S^T q (2)."""
    return 7.0 * rows * heads * head_dim * head_dim


def scan_flops(tokens: int, heads: int, head_dim: int, chunk: int) -> float:
    """The chunked recurrence over `tokens` (token, layer) pairs, padding
    included. A token and head, with C = chunk and d = head_dim: the two
    decayed Gram rows A and B (2 C d each), the triangular solve (C d),
    B u (2 C d), and three products with the [d, d] state (k S, q S and
    the state's update, 2 d d each)."""
    return float(tokens) * heads * (
        7.0 * chunk * head_dim + 6.0 * head_dim * head_dim
    )


def scan_bytes(tokens: int, heads: int, head_dim: int, chunk: int) -> float:
    """The same tokens' traffic: q, k, v, g [heads, d] and beta in and the
    output [heads, d] back a token, and a chunk's carried state in and
    its state after the chunk out (the program returns every chunk's)."""
    token = 5 * heads * head_dim + heads
    state = 2.0 * heads * head_dim * head_dim / chunk
    return ITEMSIZE * float(tokens) * (token + state)
