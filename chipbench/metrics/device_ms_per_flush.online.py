"""Device busy milliseconds per serving flush: busy time of the traced
window (``trace_reduce``) over the flushes the replicas made in it."""


def read(rec):
    flushes = rec.counters.get("flushes", 0)
    if rec.reduction is None or flushes <= 0:
        return None
    return 1e3 * rec.reduction.busy_s / flushes
