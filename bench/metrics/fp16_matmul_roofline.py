"""``fp16_matmul`` (Pallas) time against its roofline, from the trace
and ``kernels/fp16_matmul.py``."""

import roofline


def read(run):
    return roofline.share(run, "fp16_matmul")
