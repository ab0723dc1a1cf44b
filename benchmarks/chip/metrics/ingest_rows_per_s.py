"""Rows the writers had acknowledged by the window's end, over the window's elapsed time."""


def read(art):
    if not art.writers:
        return None
    return art.acked_rows / art.window_s
