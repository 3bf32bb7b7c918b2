"""Host milliseconds per serving flush: the seconds of the replicas'
``xtime.serve.flush`` spans in the traced window, less their
``xtime.serve.wait`` on the device, over the flushes in it
(``program_trace``)."""

from chipbench import program_trace


def read(rec):
    trace = program_trace.of_run(rec)
    if trace is None:
        return None
    flushes = len(program_trace.spans(trace, "xtime.serve.flush"))
    if flushes == 0:
        return None
    host = (program_trace.seconds(trace, ["xtime.serve.flush"])
            - program_trace.seconds(trace, ["xtime.serve.wait"]))
    return 1e3 * host / flushes
