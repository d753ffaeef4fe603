"""Planning: bytes of the leaves the restores of the window read as chunked entries (the
chunked_read counter: leaves larger than the chunk size, several reads into one buffer and one
upload) over the state's bytes a restore, in percent.  None where the library has no such counter."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "chunked_read")
    state = run["counters"].get("state_bytes")
    return 100.0 * p["bytes"] / (state * len(restores)) if p and restores and state else None
