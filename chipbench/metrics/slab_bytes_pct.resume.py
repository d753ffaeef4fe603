"""Planning: bytes the restores of the window took out of slab files (the slab_read
counter: slab members read, merged or into place) over the state's bytes a restore, in percent."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "slab_read")
    state = run["counters"].get("state_bytes")
    return 100.0 * p["bytes"] / (state * len(restores)) if p and restores and state else None
