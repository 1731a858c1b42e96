"""Time the server's batcher thread waits for a batch, per launch: the
sum of the telemetry's ``batch_wait`` histogram (the whole
``next_batch`` call: the wait on an empty queue and the deadline hold)
over its ``batches`` counter."""


def read(run):
    tel = run["telemetry"]
    if tel is None or "batch_wait" not in tel["latency_s"]:
        return None
    batches = tel["counters"].get("batches", 0)
    h = tel["latency_s"]["batch_wait"]
    return h["mean"] * h["count"] / batches * 1e3 if batches else None
