"""idle_share (%): the share of the traced window in which no operation
ran on the device, averaged over the cell's chips: 1 - busy / window,
busy being the union of the device-op intervals."""


def read(view):
    w = view.window_s
    if w <= 0 or not view.devices:
        return None
    return 100.0 * (1.0 - view.mean_busy_s() / w)
