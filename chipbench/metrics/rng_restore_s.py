"""Orchestration: what restoring the job's RNGState adds to a restore, planned and loaded last: the
rng_restore counter's plan_s (its plan_read wall) and tail_s (from the previous stateful's load to
its own) per restore that restored one.  None where the library has no such counter or no restore
held an RNGState."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "rng_restore")
    return (p["plan_s"] + p["tail_s"]) / p["n"] if p and "plan_s" in p and "tail_s" in p else None
