"""Host milliseconds per round in the program's ``plan_build`` and
``gather`` phases (event plan, selection, cohort gathers) of the traced
window's runs."""


def read(m):
    s = getattr(m.profile, "seconds", None)
    if not s or ("plan_build" not in s and "gather" not in s):
        return None
    return (s.get("plan_build", 0.0) + s.get("gather", 0.0)) \
        / m.work["rounds"] * 1e3
