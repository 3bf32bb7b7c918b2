"""Milliseconds a routed request waits in a replica's inbox before its
flush starts: the change of the replicas' cumulative ``inbox_wait_s``
over that of ``started_requests`` (``ClusterServer`` counters, carried on
each ``xtime.serve.flush`` span and differenced between the first and
the last flush of each replica in the traced window, summed over the
replicas; ``program_trace``)."""

from chipbench import program_trace


def read(rec):
    trace = program_trace.of_run(rec)
    if trace is None:
        return None
    d = program_trace.counter_delta(trace, "xtime.serve.flush", "replica",
                                    ("started_requests", "inbox_wait_s"))
    if d["started_requests"] <= 0:
        return None
    return 1e3 * d["inbox_wait_s"] / d["started_requests"]
