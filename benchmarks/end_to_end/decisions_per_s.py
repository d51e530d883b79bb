"""Every item answered without an error string inside the window, over
the whole window's seconds (client side)."""


def read(ctx):
    w = ctx["window"]
    return w["decisions"] / w["seconds"]
