"""Hand-tiled Pallas TPU kernels (training flash attention, ring
attention, serving decode/verify kernels)."""

from typing import Optional

# how many kernel traces resolved to each execution mode in this process
# (counted at trace time, once per compiled program). chip_smoke.py reads
# it to prove that nothing on the chip path ran in the interpreter.
TRACE_MODES = {"compiled": 0, "interpreted": 0}


def compiler_params(dimension_semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics)
    )


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place a kernel's `interpret=None` default is decided:
    compiled by Mosaic on a TPU backend, the Pallas interpreter anywhere
    else (the CPU parity tests run the exact kernel code that way). On a
    TPU `None` can therefore never mean the interpreter; an explicit
    True is a caller's debugging choice and is counted like any other."""
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    TRACE_MODES["interpreted" if interpret else "compiled"] += 1
    return bool(interpret)


def mxu_dot(a, b, contract):
    """`a` x `b` contracting dims `contract` = (a_dim, b_dim) on the MXU
    with f32 accumulation. `jax_default_matmul_precision` reaches kernel
    bodies at trace time, and under "highest" asks Mosaic for an fp32
    contraction, which it refuses for bf16 operands ("Bad lhs type"):
    bf16 operands are pinned to their native single pass, f32 operands
    keep following the setting."""
    import jax.numpy as jnp
    from jax import lax

    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None,
        preferred_element_type=jnp.float32,
    )
