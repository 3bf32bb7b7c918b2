"""Host milliseconds per ``score_file`` chunk: the seconds of the
program's per-chunk stages in the traced window (``xtime.score.read``,
``.bin``, ``.pad``, ``.dispatch``, ``.fetch``, ``.write``; the wait for
the device is left out) over the chunks dispatched in it
(``xtime.score.dispatch`` spans; ``program_trace``)."""

from chipbench import program_trace

STAGES = tuple(f"xtime.score.{s}" for s in ("read", "bin", "pad", "dispatch", "fetch", "write"))


def read(rec):
    trace = program_trace.of_run(rec)
    if trace is None:
        return None
    chunks = len(program_trace.spans(trace, "xtime.score.dispatch"))
    if chunks == 0:
        return None
    return 1e3 * program_trace.seconds(trace, STAGES) / chunks
