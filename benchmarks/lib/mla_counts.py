"""What the latent decode kernel needs, from shapes: the bytes and FLOPs
behind `latent_kernel_roofline`. One call is one layer's decode
attention: every live slot's query heads against that slot's latent rows
[c | kr], the values being a row's first `value_width` lanes. Counts are
of the algorithm: each attended row once at its OWN width (the lanes a
cache pads it with do not count, nor do a page's unused positions), each
slot's query [heads, row] in and output [heads, value_width] back."""

from __future__ import annotations


def latent_decode_bytes(
    rows: int, slot_steps: int, heads: int, row: int, value_width: int,
    itemsize: int,
) -> float:
    """`rows` latent rows attended and `slot_steps` (slot, layer) queries
    answered, both summed over the calls."""
    return itemsize * (
        float(rows) * row + float(slot_steps) * heads * (row + value_width)
    )


def latent_decode_flops(rows: int, heads: int, row: int, value_width: int) -> float:
    """q . k over the whole row and p . v over its value lanes, a head and
    an attended row: 2 m k n each."""
    return 2.0 * rows * heads * (row + value_width)
