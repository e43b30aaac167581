"""``fp16_matmul``: the Pallas GEMM ``y = x @ w`` (repro.kernels.fp16_matmul).

Its device events are named after the jitted wrapper of its
``pallas_call``; the call's shapes are read from the HLO text of the
event (the result first, then the operands).
"""

import trace_reduce

TRACE_NAMES = ("fp16_matmul_pallas",)


def cost_of_shapes(x, w, y) -> tuple:
    """(FLOPs, HBM bytes) of one call on ``x`` (M, K) and ``w`` (K, N)
    giving ``y`` (M, N), each ``(dtype, shape, in_hbm)``: 2 M N K FLOPs;
    every operand read once and the result written once, counting only
    the arrays that live in HBM (XLA may hand the kernel an operand it
    already placed in the core's memory)."""
    (_, (m, k), _), (_, (k2, n), _), _ = x, w, y
    if k != k2:
        raise ValueError(f"fp16_matmul shapes do not chain: {x} {w}")
    return 2.0 * m * n * k, float(trace_reduce.hbm_bytes(x, w, y))


def cost(ev) -> tuple:
    y, x, w = trace_reduce.call_shapes(ev)[:3]
    return cost_of_shapes(x, w, y)
