"""Set-up seconds: process start to the window's start (host clock).

Import, the world and its frames, the program, loading or compiling every
program, and the warm-up ticks.
"""


def read(run):
    return run.get("setup_s")
