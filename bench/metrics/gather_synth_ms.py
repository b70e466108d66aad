"""Host milliseconds per round in the program's ``gather/synthesize``
spans (``LazyFederatedData.gather`` of the round cohorts) of the traced
window's runs, read from their ``phase:gather/synthesize`` annotations on
the profiler trace."""


def read(m):
    s = m.reduced.span_s.get("phase:gather/synthesize")
    if not s:
        return None
    return s / m.work["rounds"] * 1e3
