"""Live ΔG lanes (inserts plus deletes) applied and repaired over the whole
window, divided by the window's whole elapsed time (host clock)."""


def read(obs):
    if not obs["batches"]:
        return None
    return obs["updates"] / obs["window_s"]
