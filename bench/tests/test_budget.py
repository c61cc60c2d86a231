"""A run's budget: a set-up or a window batch that outlives it ends the run
with exit status 1, no result line and a ``budget:`` line naming the
phase.  The process's exit is shown in child processes only."""
import os
import re
import subprocess
import sys
import textwrap
import time

import manifest
import run


def wait_for(got, seconds=10.0):
    end = time.perf_counter() + seconds
    while not got and time.perf_counter() < end:
        time.sleep(0.01)


def test_watchdog_fires_after_its_limit_and_names_the_phase():
    got = []
    w = run.Watchdog(got.append)
    try:
        t = time.perf_counter()
        w.arm(0.2, "set-up budget 0.2 s")
        w.phase("prologue")
        wait_for(got)
        took = time.perf_counter() - t
        time.sleep(0.3)
    finally:
        w.close()
    assert took >= 0.2
    assert len(got) == 1, got
    assert re.fullmatch(r"budget: prologue still running at 0\.\d s "
                        r"\(set-up budget 0\.2 s\)", got[0]), got


def test_watchdog_disarmed_in_time_does_not_fire():
    got = []
    w = run.Watchdog(got.append)
    try:
        w.arm(0.3, "set-up budget 0.3 s")
        w.phase("bind")
        time.sleep(0.1)
        w.disarm()
        time.sleep(0.5)
        assert got == []
        w.arm(0.1, "window budget 0 s + 0.1 s")   # re-armed, it fires
        w.phase("window batch 3")
        wait_for(got)
    finally:
        w.close()
    assert not w._thread.is_alive()
    assert len(got) == 1 and got[0].startswith(
        "budget: window batch 3 still running at"), got


CHILD = """
import json, sys, time
sys.path[:0] = {paths!r}
import run, test_faults
run.SETUP_BUDGET_S = {setup}
run.WINDOW_BUDGET_S = {window}
cell = test_faults.small_cell("sssp-kron18-mixed")
cell.traffic = {{**cell.traffic, "warmup": {{"min": 1, "max": 1}}}}
calls = []

def sleepy(sess, batch):
    calls.append(batch)
    if len(calls) > {normal}:
        time.sleep(600)
    sess.apply(batch)

res = run.run(cell, 2 ** 31 + 5, 1.0, False, require_tpu=False, fault=sleepy)
print(json.dumps(res), flush=True)
"""


def run_child(setup, window, normal):
    """A small run in a child process whose batch ``normal`` (counted from
    the first warm-up batch, of which there is one) sleeps."""
    paths = [str(manifest.BENCH), str(manifest.BENCH / "tests"),
             str(manifest.ROOT / "src")]
    code = CHILD.format(paths=paths, setup=setup, window=window,
                        normal=normal)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.perf_counter()
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       cwd=manifest.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    return p, time.perf_counter() - t


def test_setup_past_its_budget_exits_1_with_no_result():
    p, took = run_child(setup=5.0, window=120.0, normal=0)
    assert p.returncode == 1, p.stderr[-2000:]
    assert "{" not in p.stdout
    assert re.search(r"budget: warm-up batch 0 still running at 5\.\d s "
                     r"\(set-up budget 5 s\)", p.stderr), p.stderr[-2000:]
    assert took < 60


def test_window_batch_past_its_budget_exits_1_with_no_result():
    p, took = run_child(setup=360.0, window=2.0, normal=1)
    assert p.returncode == 1, p.stderr[-2000:]
    assert "{" not in p.stdout
    assert re.search(r"budget: window batch 0 still running at 3\.\d s "
                     r"\(window budget 1 s \+ 2 s\)", p.stderr), \
        p.stderr[-2000:]
    assert took < 60
