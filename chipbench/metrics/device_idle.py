"""Share of the traced window in which no op ran on the device, for every
``device_idle.<cell kind>`` metric: 1 - (union of device op intervals) /
window (``trace_reduce``)."""


def read(rec):
    return None if rec.reduction is None else rec.reduction.idle_pct
