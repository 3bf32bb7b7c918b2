"""Rows a serving flush carries: the window's change in the replicas'
``served_rows`` over its change in their ``flushes``
(``ClusterServer.report()``)."""


def read(rec):
    flushes = rec.counters.get("flushes", 0)
    if flushes <= 0:
        return None
    return rec.counters["served_rows"] / flushes
