"""``SnapshotManager.save(step, app_state)``: the call returns at the durable
commit.  The live state's fingerprint (reference.py) is taken just before
the call."""

import time


def run(job):
    step = job.step_index
    fp = job.fingerprint(job.state)
    fp.block_until_ready()
    job.saved_fp[step] = fp
    with job.operation("save", step=step, bytes=job.state_bytes, save_s=None) as rec:
        t0 = time.monotonic()
        job.manager.save(step, job.load.split(job.state))
        t1 = time.monotonic()
        rec["save_s"] = t1 - t0
        job.account.span("save_call", t0, t1)
