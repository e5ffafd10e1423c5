"""Share of the window in which no operation ran on the device."""


def read(view):
    return 100.0 * (1.0 - view["trace"].busy_s / view["trace"].window_s)
