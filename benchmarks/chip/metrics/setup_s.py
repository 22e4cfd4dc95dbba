"""Process start to the window's opening: imports, weights made on the
device, engine and cache, warm-up of every launch shape (compilation on a
cold cache), and the warm-up arrivals that bring the engine to steady
state before the window (host clock)."""


def read(run):
    return run.setup_s
