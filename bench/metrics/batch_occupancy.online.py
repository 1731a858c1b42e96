"""Real requests over launched slots: the server's ``dispatched``
counter over the sum of width times launches of each compiled width
(``launch_width_<w>`` counters)."""


def read(run):
    tel = run["telemetry"]
    if tel is None:
        return None
    c = tel["counters"]
    slots = sum(int(k.rsplit("_", 1)[1]) * v for k, v in c.items()
                if k.startswith("launch_width_"))
    return c.get("dispatched", 0) / slots if slots else None
