"""Set-up: process start to the first due request (weights, compiles or
cache loads, warm-up)."""


def read(w):
    return w.setup_s
