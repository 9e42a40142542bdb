"""Set-up: process start to the window's first request (generation,
store build and warm-up included)."""


def read(w):
    return w.setup_s
