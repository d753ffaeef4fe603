"""Scheduler: phase_stats host_buffer_wait wall per restore of the window: reads dispatched and
then held, in their io slot, until the host buffer their twin in the stateful before is landing
from has come back (other reads run inside it; sched_wait_s.resume does not count it).  0 where
the restores went through the pool (the host_pool counter) and no read waited; None without one."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    if not restores or not phase(run, "host_pool"):
        return None
    p = phase(run, "host_buffer_wait")
    return p["wall"] / len(restores) if p else 0.0
