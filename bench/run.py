"""One run of one benchmark cell on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is looked up in
``BENCHMARK.json``; its configuration, traffic mix and metric readers are
files under ``bench/`` (see ``manifest.py``).  A run:

  1. refuses a device that is not a TPU, fewer chips than the cell asks
     for, or a device kind missing from ``peaks.json``: exit 2, no result;
  2. keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` or
     else ``bench/.cache/jax``;
  3. makes the graph and the ΔG stream, with vertex labels permuted by
     ``--seed`` (``gen.py``), and then arms the run's budget (below);
  4. binds the configuration's DSL program through ``repro.compile`` →
     ``bind`` and arms its Dynamic function (prologue = static solve);
  5. warms up on the stream until a batch runs with no pool grow and no
     compile that missed the cache;
  6. drives a closed loop of ``Session.apply`` + ``block_until_ready`` on
     the repaired answer (the program module's ``PROPS`` and, where it
     names one, its returned ``VALUE``), starting whole batches while less
     than ``--seconds`` have passed (``--trace 1``: under the profiler);
     the budget is disarmed when the window ends;
  7. reads the answer and the alive edge set back, frees the session,
     and compares them with the numpy reference on the same prefix of the
     stream;
  8. prints the result as the last line of stdout, and each compared
     number beside its limit as the last lines of stderr.

A run's budget bounds the program's share of a run: a set-up (CSR build,
bind, prologue, warm-up) still running ``SETUP_BUDGET_S`` after the inputs
are made, or a window batch still running ``WINDOW_BUDGET_S`` after
``--seconds`` ran out, ends the run with one ``budget:`` line on stderr
naming the phase, no result line and exit status 1, as a program that
raises in its set-up ends.  So a program that cannot finish its set-up in
reasonable time fails instead of running until it is stopped.  Exit
status: 0 with a result line; 2 and no result without the chip; 1 and no
result when the program raises or outlives its budget.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import trace  # noqa: E402

CACHE = manifest.BENCH / ".cache"
sys.path.insert(0, str(manifest.ROOT / "src"))

# A run's budget.  Set-up: 6.3 times the longest first-run set-up on
# record (57.1 s, SSSP, compiles included) and under a third of the
# 1,200 s that a checkout's first run, which compiles, is allowed; so a
# program that cannot finish its set-up fails with time to spare.
SETUP_BUDGET_S = 360.0
# Window: how long a batch may run past ``--seconds``.  The longest batch
# on record is about 6.3 s (PageRank), and a 51 s window that holds 5
# batches allows 10.2 s; 120 s is far past either, and a run that ends
# there still ends inside the 360 s a run is allowed.
WINDOW_BUDGET_S = 120.0


class NoDevice(RuntimeError):
    """The machine lacks the chip the cell asks for."""


class Events:
    """Counts and summed seconds of ``jax.monitoring`` events."""

    def __init__(self):
        self.count = collections.Counter()
        self.secs = collections.Counter()

    def on_duration(self, event: str, secs: float, **_):
        self.count[event] += 1
        self.secs[event] += secs

    def on_event(self, event: str, **_):
        self.count[event] += 1

    def snapshot(self):
        return collections.Counter(self.count), collections.Counter(self.secs)

    def since(self, snap) -> dict:
        c0, s0 = snap
        return {k: [self.count[k] - c0[k], self.secs[k] - s0[k]]
                for k in self.count if self.count[k] != c0[k]}


COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def compiles(ev: dict) -> int:
    """Backend compiles that did not come from the persistent cache."""
    return ev.get(COMPILE, [0])[0] - ev.get(CACHE_HIT, [0])[0]


def check_device(jax, chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: jax.devices()[0].platform is {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found {len(devs)}")
    if d.device_kind not in manifest.peaks():
        raise NoDevice(f"device kind {d.device_kind!r} is not in peaks.json")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache(jax) -> None:
    """The compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
    else to ``bench/.cache/jax``; every program is cached, however quick to
    compile, with no size limit (so no eviction)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(*a):
    print(f"[{time.perf_counter() - T_START:9.3f}s]", *a, file=sys.stderr,
          flush=True)


def expire(message: str) -> None:
    """End a run that outlived its budget: the message on stderr, no
    result line, exit status 1.  ``os._exit``, since the phase that
    overran may hold the main thread in a call that never returns."""
    log(message)
    os._exit(1)


class Watchdog:
    """A run's budget: one sleeping thread that calls
    ``on_expire(message)`` when the armed deadline passes before
    ``disarm``.  The message names the phase running then (``phase``) and
    the seconds since the budget was armed."""

    def __init__(self, on_expire=expire):
        self._on_expire = on_expire
        self._cv = threading.Condition()
        self._deadline = None          # perf_counter time, None = disarmed
        self._origin = 0.0
        self._budget = ""
        self._phase = "set-up"
        self._closed = False
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-budget")
        self._thread.start()

    def arm(self, limit_s: float, budget: str) -> None:
        """Expire ``limit_s`` from now; ``budget`` ends the message, e.g.
        ``"set-up budget 360 s"``."""
        with self._cv:
            self._origin = time.perf_counter()
            self._deadline = self._origin + limit_s
            self._budget = budget
            self._cv.notify()

    def phase(self, name: str) -> None:
        with self._cv:
            self._phase = name

    def disarm(self) -> None:
        with self._cv:
            self._deadline = None
            self._cv.notify()

    def close(self) -> None:
        with self._cv:
            self._deadline, self._closed = None, True
            self._cv.notify()
        self._thread.join()

    def _watch(self) -> None:
        # ``on_expire`` runs under the lock, so a ``disarm`` racing with it
        # returns only once it is over (never, for ``expire``)
        with self._cv:
            while not self._closed:
                if self._deadline is None:
                    self._cv.wait()
                    continue
                now = time.perf_counter()
                if now < self._deadline:
                    self._cv.wait(self._deadline - now)
                    continue
                self._deadline = None
                self._on_expire(f"budget: {self._phase} still running at "
                                f"{now - self._origin:.1f} s "
                                f"({self._budget})")


def alive_edges(sess):
    """(edges (E, 2) int64 sorted by (src, dst), weights) alive in the
    session's diff-CSR, read from the packed device state as it is."""
    from repro.core.engine import dyn_from_state
    tree, meta = sess.engine.pack_state(sess.handle)
    if meta["kind"] == "dist":
        src, dst, w = (np.asarray(tree[k]) for k in ("src", "dst", "w"))
    else:
        g = dyn_from_state(tree if meta["kind"] == "dyn" else tree["g"],
                           meta["n"])
        es, ed, ew, ea = (np.asarray(x) for x in g.edge_arrays())
        src, dst, w = es[ea], ed[ea], ew[ea]
    e = np.stack([src, dst], axis=1).astype(np.int64)
    order = np.lexsort((e[:, 1], e[:, 0]))
    return e[order], np.asarray(w)[order]


def edges_wrong(got, want) -> int:
    """Edges present on one side only, plus edges whose weight differs."""
    (ge, gw), (we, ww) = got, want
    n = int(max(ge.max(initial=0), we.max(initial=0))) + 1
    gk, wk = ge[:, 0] * n + ge[:, 1], we[:, 0] * n + we[:, 1]
    if len(gk) == len(wk) and np.array_equal(gk, wk):
        return int((gw != ww).sum())
    common, gi, wi = np.intersect1d(gk, wk, return_indices=True)
    return int(len(gk) + len(wk) - 2 * len(common)
               + (gw[gi] != ww[wi]).sum())


def run(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
        require_tpu: bool = True, fault=None, on_expire=expire) -> dict:
    """One run; returns the result object.  ``fault(sess, batch)`` stands
    in for ``sess.apply`` (tests plant faults through it); ``on_expire``
    is called with the message if the run outlives its budget (tests
    record it in place of the process's exit)."""
    import jax
    enable_cache(jax)
    if require_tpu:
        device = check_device(jax, cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
    events = Events()
    jax.monitoring.register_event_duration_secs_listener(events.on_duration)
    jax.monitoring.register_event_listener(events.on_event)
    watchdog = Watchdog(on_expire)
    try:
        return _run(jax, cell, seed, seconds, traced, device, events, fault,
                    watchdog)
    finally:
        watchdog.close()
        jax.monitoring.unregister_event_duration_listener(events.on_duration)
        jax.monitoring.unregister_event_listener(events.on_event)


def _run(jax, cell, seed, seconds, traced, device, events, fault,
         watchdog) -> dict:
    import repro
    from repro.dsl_programs import path as program_path
    from repro.graph import build_csr
    from repro.graph.updates import UpdateStream

    cfg, traffic, prog = cell.config, cell.traffic, cell.program
    n, edges, weights, stream, perm = gen.make_inputs(cfg, traffic, seed)
    watchdog.arm(SETUP_BUDGET_S, f"set-up budget {SETUP_BUDGET_S:.0f} s")
    args = prog.run_args(cfg, perm)
    B = stream.batch
    log(f"graph: n={n} edges={len(edges)}; stream: {stream.batches} "
        f"batches of {stream.inserts} inserts + {stream.deletes} deletes "
        f"in {B} lanes")
    apply = fault or (lambda s, b: s.apply(b))
    value = getattr(prog, "VALUE", None)

    def answer(sess) -> list:
        """The repaired answer on the device: the properties and, where
        the program module names it, the function's returned value."""
        return ([sess.props[p] for p in prog.PROPS]
                + ([sess.value] if value else []))

    def step(sess, k: int) -> int:
        """Stream batch ``k`` through the session; its live lanes."""
        with jax.profiler.TraceAnnotation("bench.batch"):
            a, d = stream.rows(k)
            ub = UpdateStream(adds=a, dels=d).batch(0, B)
        with jax.profiler.TraceAnnotation("bench.apply"):
            apply(sess, ub)
        with jax.profiler.TraceAnnotation("bench.readback"):
            jax.block_until_ready(answer(sess))
        return len(a) + len(d)

    # the pool holds one pass of inserts, so the window never grows it;
    # "bind" in the configuration names any other bind argument (backend)
    watchdog.phase("bind")
    csr = build_csr(n, edges, weights)
    sess = repro.compile(program_path(cfg["program"])).bind(
        csr, capacity=max(stream.batches * stream.inserts, B),
        **cfg.get("bind", {}))
    log("bound")
    watchdog.phase("prologue")
    sess.run(cfg["function"], batchSize=B, **args)   # prologue; loop armed
    jax.block_until_ready(answer(sess))
    log("prologue done")

    wu, k = traffic["warmup"], 0
    while True:
        snap, grows = events.snapshot(), sess.health.pool_grows
        watchdog.phase(f"warm-up batch {k}")
        step(sess, k)
        k += 1
        quiet = (sess.health.pool_grows == grows
                 and compiles(events.since(snap)) == 0)
        log(f"warm-up batch {k - 1}: "
            f"{'quiet' if quiet else 'grew or compiled'}")
        if k >= wu["max"] or (k >= wu["min"] and quiet):
            break
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s:.3f} s, {k} warm-up batches")

    trace_dir = CACHE / "trace" / cell.name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    snap, h0 = events.snapshot(), sess.health.as_dict()
    batches = updates = failed = 0
    t0 = time.perf_counter()
    watchdog.arm(seconds + WINDOW_BUDGET_S,
                 f"window budget {seconds:g} s + {WINDOW_BUDGET_S:.0f} s")
    try:
        while time.perf_counter() - t0 < seconds:
            watchdog.phase(f"window batch {batches}")
            updates += step(sess, k)
            k += 1
            batches += 1
    except Exception:   # noqa: BLE001 — the run reports it as not correct
        failed = 1
        log(f"batch {k} raised:\n{traceback.format_exc()}")
    window_s = time.perf_counter() - t0
    watchdog.disarm()
    if traced:
        jax.profiler.stop_trace()
    ev = events.since(snap)
    h1 = sess.health.as_dict()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:cell.chips]]
    peak = None if None in peaks else max(peaks)
    log(f"window: {batches} batches, {updates} updates in {window_s:.3f} s; "
        f"compiles that missed the cache: {compiles(ev)}; events: "
        + json.dumps({e.rsplit("/", 1)[-1]: v for e, v in ev.items()}))

    got = {p: sess.props.host(p) for p in prog.PROPS}
    if value:
        got[value] = np.asarray(sess.value)[()]
    got_edges = alive_edges(sess)
    del sess, csr
    gc.collect()

    obs = {"setup_s": setup_s, "window_s": window_s, "batches": batches,
           "updates": updates, "peak_bytes": peak, "events": ev,
           "health": (h0, h1), "trace": None}
    if traced:
        obs["trace"] = trace.reduce(*trace.read(trace.find(str(trace_dir)),
                                                cell.chips))

    want_edges = reference.edges_after_updates(edges, weights,
                                               *stream.net(k))
    checks = {"edges_wrong": edges_wrong(got_edges, want_edges)}
    ref = prog.reference_props(n, *want_edges, args)
    checks.update(prog.compare(got, ref, *want_edges, args))
    limits = cfg["limits"]
    correct = (not failed and set(checks) == set(limits)
               and all(limits[c] is not None and checks[c] <= limits[c]
                       for c in checks))

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = manifest.Cell.reader(m).read(obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if peak is not None:
        device["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": batches,
              "failed": failed, "metrics": metrics, "device": device}
    t = obs["trace"]
    if t is not None:
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = {c: {"value": checks[c], "limit": limits.get(c)}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.Cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as exc:
        log(str(exc))
        return 2
    for c, v in result["checks"].items():
        log(f"check {c}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
