"""Roofline share of the streaming matmul kernel (``kernels/streaming.py``):
the least time of the passes the step hands it, over the device time of
the trace's operations named below (HLO instruction names without their
``.<n>`` suffix; the Pallas call is named after ``streaming_matmul``)."""

from chipbench.readers import kernel_roofline

KERNELS = ("streaming_matmul",)


def read(run):
    return kernel_roofline(run, KERNELS)
