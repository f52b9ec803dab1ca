"""Window length over the steps completed in it (host clock, profiler
off): all the work and all the time of the window."""


def read(r):
    return 1e3 * r.window_s / r.steps
