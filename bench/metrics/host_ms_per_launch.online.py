"""Host work of the server's batcher thread per launch, during which a
serial batcher leaves the device with nothing queued: the sums of the
telemetry's ``pack``, ``dispatch``, ``to_host`` and ``fulfil``
histograms over its ``batches`` counter."""

PHASES = ("pack", "dispatch", "to_host", "fulfil")


def read(run):
    tel = run["telemetry"]
    if tel is None or any(p not in tel["latency_s"] for p in PHASES):
        return None
    batches = tel["counters"].get("batches", 0)
    if not batches:
        return None
    hists = tel["latency_s"]
    return sum(hists[p]["mean"] * hists[p]["count"]
               for p in PHASES) / batches * 1e3
