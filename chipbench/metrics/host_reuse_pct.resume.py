"""H2D: of the bytes the restores of the window read into place through the restore's host
buffer pool (the host_pool counter), the share that landed in a buffer an earlier leaf had
landed from (bytes over bytes + fresh), in percent."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "host_pool")
    read_into_place = p["bytes"] + p["fresh"] if p else 0
    return 100.0 * p["bytes"] / read_into_place if read_into_place else None
