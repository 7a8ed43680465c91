"""Device: peak HBM in use over the run up to the window's close
(``peak_bytes_in_use``) as a share of what the chip lets the process
use (``bytes_limit``), on the fullest chip."""


def read(rec):
    m = rec.memory
    if not m.get("peak_bytes") or not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes"] / m["bytes_limit"]
