"""90th percentile of the wall time of the window's calls, each one
researcher's run of R rounds timed until its results are on the host."""


def read(m):
    return m.quantile(m.unit_s, 90) * 1e3
