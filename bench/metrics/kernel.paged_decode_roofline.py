"""Kernels: the Pallas paged decode-attention kernel's share of its
roofline.  For every call in the window the least time is the larger of
its operations over the bf16 peak and its bytes over the HBM bandwidth
(the block's ``paged_decode_calls`` in ``bench/blocks/<block>.py``, from
the live lengths); their sum is divided by the kernel's device time in
the trace."""

from bench.trace import is_paged_kernel


def read(rec):
    if rec.trace is None or not rec.window.paged_calls:
        return None
    s, n = rec.trace.kernel_s(is_paged_kernel)
    if not n:
        return None
    return 100.0 * rec.window.paged_roof_s / s
