"""Pipe: the pipeline's bubbles.  For each stage's device, the share of the
traced window in which it is idle while another stage's device is busy
(the union of the others' merged busy intervals less its own), averaged
over the stages.  Gaps in which every device idles (the host's) are not
bubbles; ``device.idle_frac`` counts both."""

from bench.trace import overlap, union


def read(rec):
    t = rec.trace
    if t is None or len(t.devices) < 2 or not t.window_s:
        return None
    iv = t.busy_intervals
    idle = 0.0
    for dev in t.devices:
        others = union(x for d in t.devices if d != dev for x in iv[d])
        idle += sum(e - s for s, e in others) - overlap(others, iv[dev])
    return 100.0 * idle * 1e-9 / len(t.devices) / t.window_s
