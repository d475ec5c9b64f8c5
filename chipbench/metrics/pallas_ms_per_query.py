"""Kernel (`kernels/pipeline.py`): device milliseconds of the Pallas
kernel's events (`tpu_custom_call`) in the traced window, per request."""


def read(w):
    if w.trace is None or not w.n_requests:
        return None
    ops = w.trace.kernel_ops()
    if not ops:
        return None
    return sum(d for _, d, _ in ops) * 1e-6 / w.n_requests
