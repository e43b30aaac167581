"""``flash_attention`` (Pallas) time against its roofline, from the
trace and ``kernels/flash_attention.py``."""

import roofline


def read(run):
    return roofline.share(run, "flash_attention")
