"""Device busy time of the traced window per query answered in it, in
microseconds."""


def read(run):
    t = run["trace"]
    if t is None or not run["n_completed"]:
        return None
    return t.busy_s / run["n_completed"] * 1e6
