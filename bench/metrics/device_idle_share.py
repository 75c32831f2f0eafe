"""Device: the share of the traced span in which no operation ran on the
chip (1 - union of op intervals / span). Moves ``itl_p95_ms``: idle time
between steps lengthens every gap."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
