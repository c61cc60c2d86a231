"""The device's ``peak_bytes_in_use`` after the window, in 10**6 bytes."""


def read(obs):
    if obs["peak_bytes"] is None:
        return None
    return obs["peak_bytes"] / 1e6
