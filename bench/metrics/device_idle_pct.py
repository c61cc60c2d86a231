"""Share of the traced window in which no op ran on the device."""


def read(obs):
    t = obs["trace"]
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
