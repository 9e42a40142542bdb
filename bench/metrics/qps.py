"""Answers delivered in the window per second of the window."""


def read(w):
    return w.delivered_in_window() / w.seconds
