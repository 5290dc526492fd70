"""Backend compiles jax reported inside the measured window."""


def read(run: dict) -> float | None:
    return run["compiles_in_window"]
