"""H2D: phase_stats chunk_assemble wall per restore of the window: chunked leaves between the
arrival of their first chunk and of their last, their host buffer (the restore's whole arena where
the leaf is larger than the H2D window) held and nothing of it landing yet."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "chunk_assemble")
    return p["wall"] / len(restores) if p and restores else None
