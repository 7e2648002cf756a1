"""Share of the traced window in which no op ran on the device. Reads
``device_idle_pct.stream`` and ``device_idle_pct.alone`` alike."""


def read(run):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
