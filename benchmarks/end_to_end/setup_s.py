"""Process start to the window's first timed call: native rebuild check,
daemon warm-up (compile or cache), the Loader's fill, generators up, and
the mix's warm-up traffic."""


def read(ctx):
    return ctx["setup_s"]
