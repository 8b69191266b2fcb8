"""Set-up: process start to the first due request (data, fit, warm-up,
compiles)."""


def read(rec):
    return rec.setup_s
