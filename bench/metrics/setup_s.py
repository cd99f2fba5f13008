"""Set-up: weights and data made, every shape warmed, compilation included
(seconds, host clock)."""


def read(run):
    return run.setup_s
