"""Small helpers the readers share."""


def window_restores(run):
    return [r for r in run["account"].window_operations("kill_resume") if r["ok"]]


def phase(run, name):
    p = run["phases"].get(name)
    return p if p and p.get("n") else None
