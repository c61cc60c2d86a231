"""Diff-pool grow-and-merge rounds inside the window
(change in the session's ``health.pool_grows``)."""


def read(obs):
    h0, h1 = obs["health"]
    return h1["pool_grows"] - h0["pool_grows"]
