"""Mean wait of a request in the server's queue, from submit to the
dispatch of its launch: the sum and count of the server telemetry's
``queue_wait`` histogram."""


def read(run):
    tel = run["telemetry"]
    if tel is None or "queue_wait" not in tel["latency_s"]:
        return None
    h = tel["latency_s"]["queue_wait"]
    return h["mean"] * 1e3 if h["count"] else None
