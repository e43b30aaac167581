"""``flash_attention``: the Pallas online-softmax attention
(repro.kernels.flash_attention) over ``(BH, S, D)`` operands.

Its device events are named after the jitted wrapper of its
``pallas_call``; the call's shapes are read from the HLO text of the
event (the result first, then q, k and v).
"""

import trace_reduce

TRACE_NAMES = ("flash_attention_pallas",)


def cost_of_shapes(q, k, v, o) -> tuple:
    """(FLOPs, HBM bytes) of one call, each operand
    ``(dtype, (BH, S, D), in_hbm)``: ``q k^T`` and ``p v`` over every
    (query, key) pair of each head, 4 BH S^2 D FLOPs (the kernel
    computes every block of a causal call too, so the pairs are not
    halved); q, k and v read once and the output written once, counting
    only the arrays that live in HBM."""
    _, (bh, s, d), _ = q
    return 4.0 * bh * s * s * d, float(trace_reduce.hbm_bytes(q, k, v, o))


def cost(ev) -> tuple:
    o, q, k, v = trace_reduce.call_shapes(ev)[:4]
    return cost_of_shapes(q, k, v, o)
