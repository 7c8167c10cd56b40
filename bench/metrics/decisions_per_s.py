"""Readings that reached a final verdict (accept or reject) inside the
window, per second of the window."""


def read(r):
    w = r.window
    return w.decisions / w.seconds if w.decisions else None
