"""Process start to window start: generate, build and warm the plane, fill, warm the queries."""


def read(art):
    return art.setup_s
