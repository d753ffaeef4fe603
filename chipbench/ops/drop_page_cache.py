"""fsync, then ``posix_fadvise(DONTNEED)``, every file under the snapshot
root: what a restarted process would find."""

import os
import time


def run(job):
    begin = time.monotonic()
    for dirpath, _, names in os.walk(job.root):
        for name in names:
            try:
                fd = os.open(os.path.join(dirpath, name), os.O_RDONLY)
            except OSError:
                continue
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)
    job.account.span("drop_page_cache", begin, time.monotonic())
