"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device's op intervals) / (the stretch), averaged over
the chips used."""


def read(record):
    t = record["trace"]
    if not t.get("devices") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
