"""Share of the writers' time in the window spent blocked on majors they tripped
(the plane's blocked_seconds counter, over writers x window)."""


def read(art):
    if not art.writers:
        return None
    return art.blocked_s / (art.writers * art.window_s)
