"""Host milliseconds per round in the program's ``eval/fetch`` spans (the
history lists of the evaluation replay read to the host) of the traced
window's runs, read from their ``phase:eval/fetch`` annotations on the
profiler trace."""


def read(m):
    s = m.reduced.span_s.get("phase:eval/fetch")
    if not s:
        return None
    return s / m.work["rounds"] * 1e3
