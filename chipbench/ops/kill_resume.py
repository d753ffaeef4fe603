"""Drop the live state, build the zeroed target, ``restore_latest``, one
train step to ``block_until_ready``.  The restored state's fingerprint is
dispatched before the step and read once the window has closed; the
comparison's inputs go to ``job.checked``."""

import time


def warm(job):
    import jax

    jax.block_until_ready(job.load.zero_state())


def run(job):
    job.state = None
    with job.operation("kill_resume", restore_call_s=None, resume_s=None) as rec:
        begin = time.monotonic()
        target = job.load.zero_state()
        app_state = job.load.split(target)
        del target
        t0 = time.monotonic()
        step = job.manager.restore_latest(app_state)
        t1 = time.monotonic()
        job.account.span("restore_call", t0, t1)
        if step is None:
            raise RuntimeError("restore_latest found no committed snapshot")
        state = job.load.join(app_state)
        del app_state
        fp = job.fingerprint(state)
        state_step = int(state["step"])  # before the step is given (donated) the state
        job.state, loss = job.step_fn(state, job.batch(step))
        loss.block_until_ready()
        job.step_index = step + 1
        job.checked.append(
            {
                "what": f"restore of step {step}",
                "fingerprint": fp,
                "want_fingerprint": job.saved_fp.get(step),
                "loss": loss,
                "want_loss": job.live_loss.get(step),
                "step": state_step,
                "want_step": step,
                "names": job.names,
            }
        )
        end = time.monotonic()
        rec["restore_call_s"] = t1 - t0
        rec["resume_s"] = end - begin
        job.account.span("kill_resume", begin, end)
