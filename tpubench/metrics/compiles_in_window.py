"""Backend compilations plus kernel-cache misses inside the measured
window; anything but 0 means the warm-up missed a program."""


def read(run):
    return run.compiles_in_window
