"""Milliseconds per batch in which an op ran on the device: the union of
the device's op intervals in the traced window, over the window's batches."""


def read(obs):
    t = obs["trace"]
    if t is None or not obs["batches"] or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] / obs["batches"]
