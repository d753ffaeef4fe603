"""Device: 1 - union of device-op intervals over the traced window (resume cells)."""
from chipbench.metrics._common import window_restores


def read(run):
    t = run.get("trace")
    if not window_restores(run) or not t or not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
