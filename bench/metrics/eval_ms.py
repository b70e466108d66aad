"""Host milliseconds per round in the program's ``eval`` phase (history
replay of the evaluation points) of the traced window's runs."""


def read(m):
    s = getattr(m.profile, "seconds", None)
    if not s or "eval" not in s:
        return None
    return s["eval"] / m.work["rounds"] * 1e3
