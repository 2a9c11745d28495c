"""Mean time of one scheduler tick (`step()`: admissions, then one fused
decode step), by the host clock over the window's ticks outside the
traced stretch."""


def read(record):
    if not record.get("ticks"):
        return None
    return record["tick_s"] / record["ticks"] * 1e3
