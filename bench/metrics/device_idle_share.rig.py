"""Share of the traced window in which no operation ran on the chip:
1 - the union of the device's operation intervals over the window.
Moves ``rig_fps``."""


def read(r):
    window = (r.w1 - r.w0) * 1e-9
    if window <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s() / window)
