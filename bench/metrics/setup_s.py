"""Process start to the window's opening: imports, device start-up, the
input pool, compilation (or the compile cache) and warm-up (host clock)."""


def read(run):
    return run.setup_s
