"""Share of the traced window in which no operation ran on the device, in
% (1 - busy / window, busy averaged over the chips used)."""


def read(view):
    if view.window_s <= 0 or view.busy_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s / view.window_s)
