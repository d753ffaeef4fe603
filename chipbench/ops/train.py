"""``steps`` train steps, each closed by ``block_until_ready``."""


def run(job, steps):
    job.train(int(steps))
