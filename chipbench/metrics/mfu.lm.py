"""Model FLOPs of the steps in the traced window over the window and the
chip's peak (``chipbench/work.py::model_flops``, ``chipbench/peaks.py``)."""

from chipbench.readers import mfu as read  # noqa: F401
