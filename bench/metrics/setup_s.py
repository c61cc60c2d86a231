"""Seconds from the start of the process to the start of the window:
imports, graph and stream, bind, prologue, warm-up and any compile."""


def read(obs):
    return obs["setup_s"]
