"""Kernel (`kernels/pipeline.py`): the Pallas kernel's share of its
roofline.  Least time = the bytes each call moves at the least (its
operands and results, from the shapes in the trace event,
`trace.kernel_bytes`) over the chip's HBM bandwidth (`peaks.json`);
share = summed least time / summed measured time of the kernel's events."""

from chipbench.trace import kernel_bytes


def read(w):
    if w.trace is None:
        return None
    ops = w.trace.kernel_ops()
    spent = sum(d for _, d, _ in ops) * 1e-9
    if not ops or spent <= 0:
        return None
    least = sum(kernel_bytes(name) for _, _, name in ops) \
        / w.peaks["hbm_bytes_per_s"]
    return 100.0 * least / spent
