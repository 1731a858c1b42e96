"""Share of the HBM roofline the search reaches, in percent: the bytes
every answered query had to read (bench/lib/workbytes.py) over the
device busy time of the traced window times the chip's peak HBM
bandwidth (bench/lib/peaks.py). The search reads far more bytes than it
computes operations on, so memory bounds it."""


def read(run):
    t = run["trace"]
    if t is None or t.busy_s <= 0 or not run["n_completed"]:
        return None
    peak = run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * run["necessary_bytes"] / (t.busy_s * peak)
