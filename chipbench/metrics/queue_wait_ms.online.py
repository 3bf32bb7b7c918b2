"""Milliseconds a request waits in its model's queue before the
dispatcher routes it: the change of the server's cumulative
``queue_wait_s`` over that of ``routed_requests`` (``ClusterServer``
counters, carried on each ``xtime.serve.flush`` span and differenced
between the first and the last flush of each model in the traced
window; ``program_trace``)."""

from chipbench import program_trace


def read(rec):
    trace = program_trace.of_run(rec)
    if trace is None:
        return None
    d = program_trace.counter_delta(trace, "xtime.serve.flush", "model",
                                    ("routed_requests", "queue_wait_s"))
    if d["routed_requests"] <= 0:
        return None
    return 1e3 * d["queue_wait_s"] / d["routed_requests"]
