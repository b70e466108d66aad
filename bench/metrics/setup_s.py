"""Set-up seconds: process start to the window (imports, inputs and
weights from the seed, warm-up and compilation or cache loads)."""


def read(m):
    return m.setup_s
