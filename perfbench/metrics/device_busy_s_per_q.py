"""Device-busy seconds in the traced window per question answered."""


def read(run: dict) -> float | None:
    dev = run["trace"]
    if dev is None or not run["questions"]:
        return None
    return dev["busy_s"] / run["questions"]
