"""Share of the window's fixed-point iterations that swept a small
frontier's edge lanes alone: the change in the session's
``health.sweeps_sparse`` over the change in it plus ``sweeps_dense``
(both read with the session's per-batch counter sync)."""

KEYS = ("sweeps_sparse", "sweeps_dense")


def read(obs):
    h0, h1 = obs["health"]
    if not all(k in h for h in (h0, h1) for k in KEYS):
        return None
    sparse = h1["sweeps_sparse"] - h0["sweeps_sparse"]
    dense = h1["sweeps_dense"] - h0["sweeps_dense"]
    if sparse + dense <= 0:
        return None
    return 100.0 * sparse / (sparse + dense)
