"""Share of the traced window in which no operation ran on the device."""


def read(run: dict) -> float | None:
    dev = run["trace"]
    if dev is None or dev["window_s"] <= 0:
        return None
    return 1.0 - dev["busy_s"] / dev["window_s"]
