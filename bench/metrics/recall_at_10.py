"""Mean recall@10 of every answer of the window against the exact top
10 of its query (the reference of bench/lib/reference.py)."""


def read(run):
    return run["recall"]
