"""Set-up seconds: process start to the window's open (loading, building
the fleet, compiling or loading every program, warm-up traffic)."""


def read(r):
    return r.setup_s
