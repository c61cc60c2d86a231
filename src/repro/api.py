"""The stable public API: compile once, bind to any backend, keep graph
state device-resident across calls.

This is the contract the paper's DSL promises ("one program, N generated
backends") surfaced as a first-class Python API — GraphIt's
algorithm/schedule separation, StarPlat's resident Batch-loop driver:

    import repro.api as api

    prog = api.compile("src/repro/dsl_programs/sssp.sp")
    sess = prog.bind(csr, backend="pallas", capacity="auto")

    # one-shot, same semantics as the deprecated Program.run:
    res = sess.run("DynSSSP", updateBatch=stream, batchSize=16, src=0)
    res.props["dist"]          # device array — no host sync
    res.to_host()["dist"]      # explicit numpy readback

    # long-lived streaming consumer: omit the stream to arm the Batch
    # loop, then feed ΔG batches as they arrive; graph + properties stay
    # on device between calls and `engine.prepare` runs exactly once.
    sess = prog.bind(csr, backend="jnp", capacity="auto")
    sess.run("DynSSSP", src=0)
    for batch in live_feed:
        sess.apply(batch)
        serve(sess.props["dist"])

Backends are resolved by name through ``repro.core.registry``;
``register_engine`` plugs new engines in without touching this facade.
Backend options ride ``bind(**opts)`` — e.g. the sharded backend's
mesh knobs, ``bind(csr, backend="dist_sharded", num_shards=8,
partitioner="degree")``.
Hand-staged algorithms (``repro.algos``) ride the same session via
``bind_graph`` — an algorithm-agnostic session owning the resident
handle — and its ``call``/``run_stream`` helpers.
"""
from __future__ import annotations

import functools
import pathlib
import threading
from typing import Any, Callable, Dict, Iterator, Mapping, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.core.dsl.codegen import (ArmedRun, CodegenError, Program,
                                    compile_source)
from repro.core.engine import Engine, state_to_csr
from repro.core.registry import (available_backends, failover_chain,
                                 make_engine, register_engine)
from repro.graph.csr import CSR
from repro.graph.updates import UpdateBatch, UpdateStream
from repro.runtime import faults as _faults
from repro.runtime import watchdog as _watchdog
from repro.runtime.admission import DEFAULT_MAX_BATCH, AdmissionGuard
from repro.runtime.errors import (AdmissionError, DivergenceError,
                                  KernelFailure, PoolOverflowError)
from repro.runtime.failover import FailoverPolicy
from repro.runtime.health import SessionHealth

__all__ = [
    "compile", "CompiledProgram", "Session", "GraphSession", "bind_graph",
    "SessionResult", "PropertyView", "register_engine",
    "available_backends", "restore_session",
    "AdmissionError", "PoolOverflowError", "KernelFailure",
    "DivergenceError", "SessionHealth",
]

_DEFAULT_CAPACITY = 64

# lru_cache's dict ops are GIL-atomic, but a miss is not: two threads
# binding the same program race compile_source and one result is thrown
# away — and CompiledProgram identity is the pool's grouping key, so the
# loser's sessions would land in a different group.  Serialize misses.
_COMPILE_LOCK = threading.Lock()


@functools.lru_cache(maxsize=256)
def _compile_once(source_or_path: str, stamp) -> "CompiledProgram":
    return CompiledProgram(compile_source(source_or_path))


def _compile_cached(source_or_path: str, stamp) -> "CompiledProgram":
    with _COMPILE_LOCK:
        return _compile_once(source_or_path, stamp)


def compile(source_or_path: str) -> "CompiledProgram":
    """Compile DSL text (or a path to a ``.sp`` file) once; the result
    is cached per source (``.sp`` cache entries key on the file's
    mtime, so on-disk edits recompile)."""
    s = str(source_or_path)
    stamp = None
    if s.endswith(".sp"):
        p = pathlib.Path(s)
        if p.exists():
            stamp = p.stat().st_mtime_ns
    return _compile_cached(s, stamp)


def _dedupe_chain(names) -> tuple:
    """Order-preserving dedupe of a failover candidate list.  A chain
    like ``(jnp, pallas, jnp)`` (user-supplied, or a custom chain that
    re-lists the requested backend) used to construct — and on total
    failure, report — the same backend twice."""
    seen = set()
    out = []
    for name in names:
        if name not in seen:
            seen.add(name)
            out.append(name)
    return tuple(out)


def _make_engine_failover(backend: str, failover, **backend_opts):
    """Instantiate ``backend``; with failover enabled, a factory that
    raises (missing accelerator, import error) falls down the chain at
    bind time.  Returns ``(engine, bound_registry_name)``."""
    if not failover:
        return make_engine(backend, **backend_opts), backend
    chain = failover_chain(backend) if failover is True else tuple(failover)
    last = None
    for name in _dedupe_chain((backend, *chain)):
        try:
            # backend_opts are engine-specific (e.g. pallas k=): only
            # the requested backend gets them
            opts = backend_opts if name == backend else {}
            return make_engine(name, **opts), name
        except Exception as e:       # noqa: BLE001 — bind-time failover
            last = e
    raise KernelFailure(
        f"no backend in {_dedupe_chain((backend, *chain))} could be "
        f"constructed", backend=backend, cause=last)


def _post_bind_failover(sess: "GraphSession", requested: str, bound: str,
                        failover) -> None:
    """Record a bind-time degradation (the requested backend's factory
    failed and a fallback was bound instead)."""
    if bound == requested or not failover:
        return
    chain = failover_chain(requested) if failover is True \
        else _dedupe_chain(failover)
    sess._failover = FailoverPolicy(requested, chain)
    sess._failover.degraded_from()
    sess._health.preferred_backend = requested
    sess._health.backend = bound
    sess._health.failovers += 1


def bind_graph(csr: CSR, backend: str = "jnp",
               capacity: Union[str, int] = "auto",
               admission: Optional[str] = "clamp",
               max_batch: int = DEFAULT_MAX_BATCH,
               dead_letter: int = 64,
               failover=None,
               **backend_opts) -> "GraphSession":
    """An algorithm-agnostic session (no DSL program): a device-resident
    graph handle for hand-staged ``repro.algos`` code.

    ``admission`` / ``max_batch`` / ``dead_letter`` configure the ΔG
    admission guard (policy ``reject | clamp | quarantine | off``;
    DESIGN.md §6); ``failover=True`` (or an explicit chain of registry
    names) arms graceful backend degradation."""
    engine, bound = _make_engine_failover(backend, failover, **backend_opts)
    sess = GraphSession(engine, csr, capacity, backend_name=bound,
                        admission=admission, max_batch=max_batch,
                        dead_letter=dead_letter, failover=failover)
    _post_bind_failover(sess, backend, bound, failover)
    return sess


def _auto_capacity(stream: Optional[UpdateStream] = None,
                   batch: Optional[UpdateBatch] = None) -> int:
    """Diff-pool size derived from the bound stream/batch: every add may
    land in the pool (deletes only tombstone), doubled for headroom.
    With neither in sight — arming a Batch loop prepares the graph for
    the prologue before any update exists — the pool starts at the
    default.  The grow-on-overflow path backstops all underestimates.

    Every path floors at ``_DEFAULT_CAPACITY``: the stream path used to
    floor at 16, so tiny streams (e.g. a 4-add probe stream) prepared a
    pool 4x smaller than an armed session's, and the first real batch
    paid a grow-merge-replay an identically-bound armed session never
    saw."""
    if stream is not None:
        return max(_DEFAULT_CAPACITY, 2 * stream.num_adds)
    if batch is not None:
        return max(_DEFAULT_CAPACITY, 8 * batch.size)
    return _DEFAULT_CAPACITY


def _tree_spec(tree):
    """Per-leaf ``[shape, dtype]`` mirror of a nested-dict array tree —
    JSON-able, enough to rebuild an example tree for ``ckpt.restore``
    without needing the (unrecoverable) pickled treedef."""
    if isinstance(tree, dict):
        return {k: _tree_spec(v) for k, v in tree.items()}
    return [list(np.shape(tree)),
            str(getattr(tree, "dtype", np.asarray(tree).dtype))]


def _example_from_spec(spec):
    if isinstance(spec, dict):
        return {k: _example_from_spec(v) for k, v in spec.items()}
    shape, dtype = spec
    return jnp.zeros(tuple(shape), np.dtype(dtype))


def _fit_pad(arr, n_real: int, n_pad: int):
    """Refit a saved vertex array to the restoring engine's padding
    (dist n_pad = block·P changes with the device count).  The pad
    region is dead for forall lowerings — lowering masks them with
    ``idx < n_real`` — so it is filled from the saved pad value when one
    exists, else dtype-zero."""
    arr = jnp.asarray(arr)
    if arr.ndim == 0 or arr.shape[0] == n_pad:
        return arr
    body = arr[:n_real]
    if n_pad == n_real:
        return body
    fill = arr[n_real] if arr.shape[0] > n_real else jnp.zeros((), arr.dtype)
    return jnp.concatenate(
        [body, jnp.full((n_pad - n_real,), fill, arr.dtype)])


class PropertyView(Mapping):
    """Lazy view over a session's vertex properties.

    Indexing returns the **device** array (padded; no host sync);
    ``to_host()`` / ``host(name)`` perform the explicit numpy readback,
    sliced to the real vertex count — the one place the API syncs."""

    def __init__(self, arrays: Dict[str, Any], n_real: int):
        self._arrays = arrays
        self._n = n_real

    def __getitem__(self, name: str):
        return self._arrays[name]

    def __iter__(self) -> Iterator[str]:
        return iter(self._arrays)

    def __len__(self) -> int:
        return len(self._arrays)

    def host(self, name: str) -> np.ndarray:
        return np.asarray(self._arrays[name])[: self._n]

    def to_host(self) -> Dict[str, np.ndarray]:
        return {k: self.host(k) for k in self._arrays}

    def __repr__(self):
        return (f"PropertyView({sorted(self._arrays)}, "
                f"n={self._n}, device-resident)")


class SessionResult:
    """What ``Session.run`` returns: device-resident props + the DSL
    return value.  ``to_host()`` is the explicit sync point."""

    def __init__(self, session: "GraphSession", props: PropertyView,
                 value: Any = None):
        self.session = session
        self.props = props
        self.value = value

    @property
    def graph(self):
        return self.session.handle

    def to_host(self) -> Dict[str, np.ndarray]:
        return self.props.to_host()

    def __repr__(self):
        return (f"SessionResult(props={sorted(self.props)}, "
                f"value={self.value!r})")


class GraphSession:
    """Owns one engine instance and its device-resident graph handle.

    ``prepare`` runs exactly once per session — lazily, so
    ``capacity='auto'`` can wait for the first stream/batch to size the
    diff pool.  Structural updates, hand-staged drivers, and the fused
    stream executor all route through here and keep the handle warm.
    """

    # grow-and-replay attempts before _retry_on_overflow gives up with
    # PoolOverflowError (capacity doubles each attempt, so 8 attempts =
    # 256x the starting pool — past that the batch is hostile, not big)
    _max_grow_attempts = 8

    def __init__(self, engine: Engine, csr: CSR,
                 capacity: Union[str, int] = "auto", *,
                 backend_name: Optional[str] = None,
                 admission: Optional[str] = "clamp",
                 max_batch: int = DEFAULT_MAX_BATCH,
                 dead_letter: int = 64,
                 failover=None):
        if not (capacity == "auto" or isinstance(capacity, int)):
            raise ValueError(f"capacity must be 'auto' or an int, "
                             f"got {capacity!r}")
        self._engine = engine
        self._csr = csr
        self._capacity = capacity
        self._handle = None
        self._props: Dict[str, Any] = {}
        # last host-observed overflow counter (see _retry_on_overflow)
        self._of_base = 0
        # sweep counts of the engines failover left behind
        self._sweeps_base = (0, 0)
        # ΔG batches applied through apply()/run_stream() — the resume
        # position checkpointed by save()
        self._cursor = 0
        # -- fault runtime (DESIGN.md §6) ----------------------------------
        # engines share Engine.name across registry entries (pallas and
        # pallas_chained are both "pallas"), so the session keeps the
        # registry name it was bound under — the failover chain keys on it
        self._backend_name = backend_name or engine.name
        self._health = SessionHealth(backend=self._backend_name,
                                     preferred_backend=self._backend_name)
        self._guard = AdmissionGuard(admission, max_batch=max_batch,
                                     dead_letter=dead_letter,
                                     health=self._health)
        self._health.dead_letter = self._guard.buffer
        if failover:
            chain = failover_chain(self._backend_name) if failover is True \
                else _dedupe_chain(failover)
            self._failover: Optional[FailoverPolicy] = FailoverPolicy(
                self._backend_name, chain)
        else:
            self._failover = None

    # -- resident state ------------------------------------------------------
    @property
    def engine(self) -> Engine:
        return self._engine

    @property
    def backend(self) -> str:
        return self._engine.name

    @property
    def backend_name(self) -> str:
        """The registry name this session is currently bound under
        (distinct from ``backend``/``Engine.name``: pallas_chained
        binds a PallasEngine whose ``name`` is also "pallas")."""
        return self._backend_name

    @property
    def health(self) -> SessionHealth:
        """Live fault-runtime counters (admission, overflow retries,
        failovers, watchdog probes) — ``health.as_dict()`` is the
        JSON-able snapshot a serving layer scrapes."""
        self._health.backend = self._backend_name
        return self._health

    @property
    def dead_letter(self):
        """Quarantined-batch records (bounded; oldest evicted first)."""
        return self._guard.buffer.records()

    @property
    def handle(self):
        """The device-resident graph handle (prepared on first access)."""
        self._ensure_prepared()
        return self._handle

    @property
    def prepared(self) -> bool:
        return self._handle is not None

    def _ensure_prepared(self, stream: Optional[UpdateStream] = None,
                         batch: Optional[UpdateBatch] = None) -> None:
        if self._handle is not None:
            return
        cap = self._capacity if isinstance(self._capacity, int) \
            else _auto_capacity(stream, batch)
        self._handle = self._engine.prepare(self._csr, diff_capacity=cap)

    @property
    def props(self) -> PropertyView:
        """Current vertex properties, device-resident; ``.to_host()``
        syncs explicitly.  Empty until the session has run something."""
        if self._handle is None:
            return PropertyView({}, 0)
        return PropertyView(dict(self._props), self._engine.n_real)

    def _sync_counters(self) -> tuple:
        """ONE host readback of the (overflow, used, dead) pool triple;
        the engine's sweep counts ride along into ``health``."""
        _faults.fire("counter_sync", engine=self._backend_name)
        counters = self._engine.handle_counters(self._handle)
        sweeps = self._engine.sweep_counts
        if sweeps is not None:
            counters = jnp.concatenate([counters, sweeps])
        host = [int(x) for x in np.asarray(counters)]
        if sweeps is not None:
            self._health.sweeps_dense = self._sweeps_base[0] + host[3]
            self._health.sweeps_sparse = self._sweeps_base[1] + host[4]
        return tuple(host[:3])

    def _n_vertices(self) -> int:
        """Real vertex count, available before AND after prepare (a
        restored session has a handle but no CSR)."""
        return self._engine.n_real if self._handle is not None \
            else self._csr.n

    def _retry_on_overflow(self, attempt: Callable[[], None],
                           regrow: Callable[[], None],
                           batch=None,
                           rollback: Optional[Callable[[], None]] = None
                           ) -> None:
        """The one grow-on-overflow backstop: run ``attempt()`` (which
        mutates session state); while it raised the overflow counter,
        ``regrow()`` (roll back + grow the pool) and replay — **bounded**
        to ``_max_grow_attempts`` grows, after which ``rollback()``
        restores the pre-batch state and :class:`PoolOverflowError`
        carries the offending batch + pool stats out (growing until OOM
        is how a hostile batch used to take the whole process down).
        ``rollback()`` also runs if an attempt raises (an injected
        kernel fault mid-batch must not leave half-applied state).

        Exactly one counter sync per attempt: the triple is read once
        *post*-attempt and compared against the running ``_of_base``
        (the pre+post pair this replaces reintroduced the per-batch host
        sync PR 6's debt #4 removed from ``run_stream``)."""
        def run_attempt():
            try:
                attempt()
            except BaseException:
                if rollback is not None:
                    rollback()
                raise

        run_attempt()
        of = self._sync_counters()[0]
        grows = 0
        while of > self._of_base:
            self._health.overflow_retries += 1
            if grows >= self._max_grow_attempts:
                if rollback is not None:
                    rollback()
                counters = self._sync_counters()
                cap = self._engine._diff_capacity(self._handle)
                err = PoolOverflowError(
                    f"batch still overflows the diff pool after "
                    f"{grows} grow-and-replay attempts "
                    f"(capacity now {cap}); state rolled back to the "
                    f"pre-batch graph", batch=batch, attempts=grows,
                    diff_capacity=cap, counters=counters)
                self._health.record_error(err)
                raise err
            regrow()
            grows += 1
            self._health.pool_grows += 1
            self._of_base = 0  # grow merges the pool, clearing counters
            run_attempt()
            of = self._sync_counters()[0]
        self._of_base = of

    # -- graceful backend degradation (DESIGN.md §6) -------------------------
    def _guarded(self, op: Callable[[], Any]):
        """Run ``op`` with backend failover: a kernel/compile failure
        hops down the failover chain (migrating device state through
        ``state_to_csr``) and replays ``op`` on the survivor.  The typed
        data-plane faults (admission, pool overflow, divergence) pass
        through — they are the stream's fault, not the backend's.  ``op``
        must read ``self._engine`` / ``self._handle`` fresh so a replay
        sees the migrated state."""
        if self._failover is None:
            try:
                return op()
            except (AdmissionError, PoolOverflowError, DivergenceError):
                raise
            except Exception as exc:   # noqa: BLE001 — health bookkeeping
                self._health.kernel_failures += 1
                self._health.record_error(exc)
                raise
        self._maybe_reprobe()
        try:
            return op()
        except (AdmissionError, PoolOverflowError, DivergenceError):
            raise
        except Exception as exc:       # noqa: BLE001 — failover boundary
            return self._degrade_and_retry(op, exc)

    def _degrade_and_retry(self, op: Callable[[], Any], exc: Exception):
        self._health.kernel_failures += 1
        self._health.record_error(exc)
        last = exc
        for name in self._failover.candidates(self._backend_name):
            try:
                self._migrate(name)
            except Exception as mexc:  # noqa: BLE001 — try next in chain
                last = mexc
                continue
            self._failover.degraded_from()
            self._health.failovers += 1
            try:
                return op()
            except (AdmissionError, PoolOverflowError, DivergenceError):
                raise
            except Exception as nexc:  # noqa: BLE001 — keep degrading
                self._health.kernel_failures += 1
                self._health.record_error(nexc)
                last = nexc
        err = KernelFailure(
            f"backend {self._failover.preferred!r} and its failover "
            f"chain {tuple(self._failover.chain)} all failed",
            backend=self._backend_name, cause=last)
        self._health.record_error(err)
        raise err

    def _maybe_reprobe(self) -> None:
        """Sticky degradation with periodic re-probe: once the backoff
        window since the last failure elapses, try converting back to
        the preferred backend; a failed probe doubles the window."""
        if (self._backend_name == self._failover.preferred
                or not self._failover.should_probe()):
            return
        self._health.reprobes += 1
        try:
            self._migrate(self._failover.preferred)
        except Exception as exc:       # noqa: BLE001 — probe failed
            self._failover.probe_failed()
            self._health.record_error(exc)
        else:
            self._failover.recovered()

    def _migrate(self, name: str) -> None:
        """Re-bind this session's device state onto backend ``name``
        through the cross-backend conversion path (PR 7):
        ``pack_state`` (pure data access — works even when the source
        backend's kernels are broken) → host → ``state_to_csr`` →
        ``prepare`` on the new engine, properties re-placed per the new
        engine's padding.  Value-preserving; pool layout resets."""
        self._ensure_prepared()
        old = self._engine
        n = old.n_real
        tree, hmeta = old.pack_state(self._handle)
        tree = jax.tree_util.tree_map(np.asarray, tree)
        props = {k: np.asarray(v)[:n] for k, v in self._props.items()}
        csr, cap = state_to_csr(tree, hmeta)
        engine = make_engine(name)
        handle = engine.prepare(csr, diff_capacity=cap)
        self._sweeps_base = (self._health.sweeps_dense,
                             self._health.sweeps_sparse)
        self._engine = engine
        self._handle = handle
        self._backend_name = name
        self._health.backend = name
        self._props = {k: engine.put_vertex_array(
            _fit_pad(v, n, engine.n_pad)) for k, v in props.items()}
        self._of_base = self._sync_counters()[0]

    # -- divergence watchdog -------------------------------------------------
    def _watch(self, arrays: Dict[str, Any], where: str) -> None:
        if self._guard.policy != "off":
            _watchdog.check(arrays.items(), where=where,
                            health=self._health)

    def check_divergence(self) -> None:
        """On-demand NaN/Inf probe over the resident property arrays;
        raises :class:`DivergenceError` naming the poisoned ones."""
        _watchdog.check(self._props.items(), where="check_divergence",
                        health=self._health)

    # -- structural updates --------------------------------------------------
    def apply(self, batch: UpdateBatch) -> "GraphSession":
        """Apply one ΔG batch structurally (deletes then adds), after
        admission (reject/clamp/quarantine — see ``bind_graph``), growing
        the diff pool and replaying on overflow."""
        admitted = self._admit_for_apply(batch)
        if admitted is not None:
            self._apply_admitted(admitted)
        return self

    def _admit_for_apply(self, batch: UpdateBatch) -> Optional[UpdateBatch]:
        """The admission half of :meth:`apply`: guard the batch and do
        the quarantine/empty-skip cursor bookkeeping.  Returns the
        admitted batch, or None when the batch was consumed without
        device work.  Split out so the serving pool admits on its own
        thread and executes through the batched path while staying on
        the exact code (and health accounting) a solo ``apply`` uses."""
        self._ensure_prepared(batch=batch)
        admitted = self._guard.admit(batch, self._n_vertices(),
                                     cursor=self._cursor)
        if admitted is None:           # quarantined: consumed, not applied
            self._cursor += 1
            return None
        if self._guard.policy != "off" and not (
                np.asarray(admitted.add_mask).any()
                or np.asarray(admitted.del_mask).any()):
            # zero active lanes: a masked-out scatter is a device no-op,
            # so skip the launch entirely (structural path only — the
            # armed path runs every batch body for one-shot bit-equality)
            self._health.empty_skipped += 1
            self._cursor += 1
            return None
        return admitted

    def _apply_admitted(self, admitted: UpdateBatch) -> None:
        """The execution half of :meth:`apply`: deletes-then-adds under
        the failover guard with the bounded grow-and-replay backstop."""

        def work():
            base = self._handle

            def attempt():
                h = self._engine.update_del(base, admitted)
                self._handle = self._engine.update_add(h, admitted)

            def regrow():
                nonlocal base
                base = self._handle = self._engine.grow(base)

            def rollback():
                self._handle = base

            self._retry_on_overflow(attempt, regrow, batch=admitted,
                                    rollback=rollback)

        self._guarded(work)
        self._cursor += 1

    # -- hand-staged drivers -------------------------------------------------
    def call(self, fn: Callable, *args, **kwargs):
        """Run a hand-staged driver ``fn(engine, handle, *args)`` (the
        ``repro.algos`` convention).  A ``(new_handle, result)`` return
        — recognized by the first element having the session's handle
        type — is adopted into the session; anything else passes
        through untouched."""
        self._ensure_prepared()
        ret = {}

        def work():
            base = self._handle

            def attempt():
                self._handle = base
                out = fn(self._engine, base, *args, **kwargs)
                if isinstance(out, tuple) and len(out) == 2 and \
                        type(out[0]) is type(base):
                    self._handle, result = out
                    if isinstance(result, dict):
                        self._props = dict(result)
                    ret["value"] = result
                else:
                    ret["value"] = out

            def regrow():
                # the driver overflowed the pool: grow it and re-run the
                # driver from the grown pre-call graph
                nonlocal base
                base = self._engine.grow(base)

            def rollback():
                self._handle = base

            self._retry_on_overflow(attempt, regrow, rollback=rollback)

        self._guarded(work)
        return ret["value"]

    def run_stream(self, stream: UpdateStream, batch_size: int,
                   step_fn: Callable, carry, **kw):
        """Drive a stream through the engine's fused executor
        (``Engine.run_stream``); the updated handle stays resident and
        the final carry is returned.

        Admission runs as ONE vectorized host pass over the raw stream
        arrays before any device work — a clean stream (the common case)
        then takes the fused path untouched.  Poison batches are spliced
        out per policy and the surviving contiguous ranges still run
        fused (``UpdateStream.window`` keeps batch boundaries
        lane-identical)."""
        self._ensure_prepared(stream=stream)
        nb = stream.num_batches(batch_size)
        poison = self._guard.inspect_stream(stream, batch_size,
                                            self._n_vertices())
        if poison:
            carry = self._run_stream_guarded(stream, batch_size, step_fn,
                                             carry, poison, **kw)
        else:
            if self._guard.policy != "off":
                self._health.admitted += nb

            def op(c=carry):
                return self._engine.run_stream(self._handle, stream,
                                               batch_size, step_fn, c,
                                               **kw)

            self._handle, carry = self._guarded(op)
            # the fused executor may have grown/merged internally —
            # resync the overflow base with one triple read
            self._of_base = self._sync_counters()[0]
            self._cursor += nb
        if isinstance(carry, dict):
            self._props = dict(carry)
            self._watch(carry, where="run_stream")
        return carry

    def _apply_step(self, batch: UpdateBatch, step_fn: Callable, carry,
                    **kw):
        """One per-batch stream step (the poison-splice path): the
        baseline executor's body for a single admitted batch, with the
        bounded grow-and-replay backstop."""
        out = {}

        def attempt():
            view = self._engine.stream_view()
            h, c = step_fn(view, base[0], batch, carry)
            self._handle = h
            out["carry"] = c

        base = [self._handle]

        def regrow():
            base[0] = self._handle = self._engine.grow(base[0])

        def rollback():
            self._handle = base[0]

        self._retry_on_overflow(attempt, regrow, batch=batch,
                                rollback=rollback)
        return out["carry"]

    def _run_stream_guarded(self, stream: UpdateStream, batch_size: int,
                            step_fn: Callable, carry, poison, **kw):
        """Poison batches present: under ``reject`` fail fast before any
        device work; otherwise walk the stream, running clean contiguous
        ranges through the fused executor and resolving each poison
        batch individually (clamp → sanitize + single step; quarantine →
        dead-letter + skip, cursor still advancing — the batch was
        *consumed*, keeping durable-resume alignment)."""
        n = self._n_vertices()
        nb = stream.num_batches(batch_size)
        if self._guard.policy == "reject":
            first = min(poison)
            self._guard.resolve(stream.batch(first, batch_size),
                                poison[first], self._cursor, first, n)
            raise AssertionError("reject policy must raise")   # pragma: no cover
        i = 0
        while i < nb:
            if i in poison:
                admitted = self._guard.resolve(
                    stream.batch(i, batch_size), poison[i],
                    self._cursor, i, n)
                if admitted is not None:
                    carry = self._guarded(
                        lambda b=admitted, c=carry:
                        self._apply_step(b, step_fn, c, **kw))
                self._cursor += 1
                i += 1
            else:
                j = i
                while j < nb and j not in poison:
                    j += 1
                sub = stream.window(batch_size, i, j - i)
                self._health.admitted += j - i

                def op(s=sub, c=carry):
                    return self._engine.run_stream(self._handle, s,
                                                   batch_size, step_fn,
                                                   c, **kw)

                self._handle, carry = self._guarded(op)
                self._cursor += j - i
                i = j
        self._of_base = self._sync_counters()[0]
        return carry

    def to_host(self) -> Dict[str, np.ndarray]:
        return self.props.to_host()

    # -- durability (DESIGN.md §5) -------------------------------------------
    @property
    def stream_cursor(self) -> int:
        """ΔG batches applied through ``apply``/``run_stream`` so far —
        the resume position recorded by ``save``."""
        return self._cursor

    def state_tree(self):
        """Everything a durable restore needs, as one flattenable
        ``(nested-dict array tree, JSON-able meta)`` pair: the packed
        graph handle, the device-resident property arrays, and the
        stream cursor."""
        self._ensure_prepared()
        handle_tree, handle_meta = self._engine.pack_state(self._handle)
        tree = {"handle": handle_tree, "props": dict(self._props)}
        meta = {"version": 1, "kind": "graph",
                "backend": self._engine.name,
                "n": self._engine.n_real, "n_pad": self._engine.n_pad,
                "handle": handle_meta, "cursor": self._cursor}
        return tree, meta

    def save(self, ckpt_dir, step: Optional[int] = None, keep: int = 3):
        """Durably checkpoint the session (atomic-rename commit protocol,
        see ``repro.ckpt.checkpoint``).  ``step`` defaults to the stream
        cursor, so successive saves of a streaming session are ordered;
        returns the committed step directory."""
        tree, meta = self.state_tree()
        meta["tree_spec"] = _tree_spec(tree)
        step = self._cursor if step is None else int(step)
        return ckpt.save(ckpt_dir, step, tree, extra=meta, keep=keep)

    @staticmethod
    def restore(ckpt_dir, backend: Optional[str] = None,
                step: Optional[int] = None, **backend_opts):
        """Rebuild a session from ``save()`` output — see
        :func:`restore_session`."""
        return restore_session(ckpt_dir, backend=backend, step=step,
                               **backend_opts)


class Session(GraphSession):
    """A CompiledProgram bound to one backend + one graph.

    Two modes per DSL function:

    * **one-shot** — ``run("DynSSSP", updateBatch=stream, ...)`` executes
      the whole function (prologue, Batch loop over the given stream,
      epilogue) against the resident handle; bit-identical to the
      deprecated ``Program.run`` but with no re-prepare and no implicit
      host readback.
    * **armed** — omit the ``updates<g>`` argument and ``run`` executes
      only the prologue (e.g. the static algorithm), leaving the Batch
      loop armed: each ``apply(batch)`` then executes one loop body
      against the live state, and ``run_stream(stream, batch_size)``
      folds a whole stream through it.  N applies are bit-identical to
      one one-shot run over the same N batches.
    """

    def __init__(self, compiled: "CompiledProgram", engine: Engine,
                 csr: CSR, capacity: Union[str, int] = "auto", **runtime_kw):
        super().__init__(engine, csr, capacity, **runtime_kw)
        self.compiled = compiled
        self._armed: Optional[ArmedRun] = None
        # binding caches the staged per-(func, engine) executables, so
        # repeat calls skip host-side AST pattern interpretation
        self._staged_funcs: Dict[str, Any] = {}

    # -- DSL execution -------------------------------------------------------
    def _staged(self, func: str):
        """The staged executable for ``func`` on the CURRENT engine
        (failover migration clears the cache, so always resolve late)."""
        st = self._staged_funcs.get(func)
        if st is None:
            st = self._staged_funcs[func] = \
                self.compiled.program.stage(func, self._engine)
        return st

    def _batch_size_hint(self, staged, args) -> Optional[int]:
        """The batch size a one-shot run will use, when statically
        determinable host-side: Batch statements name their size
        (usually a scalar param like ``batchSize``), so the caller's
        args resolve it before execution.  None = undeterminable (the
        admission guard then admits the one-shot path unchecked)."""
        sizes = set()
        for name in staged._armable:
            v = args.get(name) if isinstance(name, str) else name
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, np.integer)):
                sizes.add(int(v))
        return sizes.pop() if len(sizes) == 1 else None

    def run(self, func: str, **args) -> SessionResult:
        """Execute DSL function ``func`` against the resident graph.

        Scalars and the update stream are passed by parameter name, as
        keyword arguments.  If the function takes an ``updates<g>``
        parameter and it is omitted (or None), the session arms the
        Batch loop instead of running it (see class docstring)."""
        program = self.compiled.program
        fnode = program.ast.func(func)   # raises early on unknown names
        upd_params = [p.name for p in fnode.params
                      if p.type.name == "updates"]
        streams = [args[p] for p in upd_params
                   if args.get(p) is not None]
        staged = self._staged(func)
        self._ensure_prepared(stream=streams[0] if streams else None)

        if upd_params and not streams:
            def arm():
                armed = self._staged(func).begin(self._handle, args)
                self._armed = armed
                self._handle = armed.gbox.value
                self._props = armed.device_props()

            self._guarded(arm)
            return SessionResult(self, self.props, value=None)

        if streams and self._guard.policy != "off":
            res = self._run_oneshot_guarded(func, staged, args, upd_params)
            if res is not None:
                return res

        out = {}

        def op():
            st = self._staged(func)
            base = self._handle

            def attempt():
                g, props, ret = st.call(base, args)
                self._handle = g
                out["props"], out["ret"] = props, ret

            def regrow():
                # adds were dropped: grow the pool and replay the whole
                # run from the pre-run graph (same backstop as
                # apply/run_stream)
                nonlocal base
                base = self._engine.grow(base)

            def rollback():
                self._handle = base

            self._retry_on_overflow(attempt, regrow, rollback=rollback)

        self._guarded(op)
        # disarm only now: a run that raised (bad args, lowering error)
        # must leave a previously armed loop intact
        self._armed = None
        self._props = out["props"]
        self._watch(self._props, where=f"run({func})")
        return SessionResult(self, self.props, value=out["ret"])

    def _run_oneshot_guarded(self, func: str, staged, args,
                             upd_params) -> Optional[SessionResult]:
        """Admission for one-shot runs: inspect the stream host-side
        before execution.  A clean stream returns None — the caller
        takes the normal one-shot path bit-exactly.  With poison
        batches, ``reject`` raises; clamp/quarantine fall back to
        arming the Batch loop and feeding guarded per-batch applies
        (documented bit-identical to one-shot over the same batches)."""
        if len(upd_params) != 1:
            return None
        pname = upd_params[0]
        stream = args[pname]
        bs = self._batch_size_hint(staged, args)
        if bs is None or not isinstance(stream, UpdateStream):
            return None
        poison = self._guard.inspect_stream(stream, bs, self._n_vertices())
        if not poison:
            self._health.admitted += stream.num_batches(bs)
            return None
        arm_args = {k: v for k, v in args.items() if k != pname}

        def arm():
            armed = self._staged(func).begin(self._handle, arm_args)
            self._armed = armed
            self._handle = armed.gbox.value
            self._props = armed.device_props()

        self._guarded(arm)
        self._armed_stream_loop(stream, bs)
        value = self._armed.value()
        self._armed = None
        self._watch(self._props, where=f"run({func})")
        return SessionResult(self, self.props, value=value)

    @property
    def armed(self) -> bool:
        return self._armed is not None

    def call(self, fn: Callable, *args, **kwargs):
        out = super().call(fn, *args, **kwargs)
        # a hand-staged driver advancing the handle would leave an armed
        # frame's graph box stale (a later apply() would silently revert
        # its updates) — successful hand-staged execution supersedes the
        # armed loop; a driver that raised leaves it intact
        self._armed = None
        return out

    @property
    def value(self):
        """The DSL return value as of the current state (armed sessions
        evaluate the post-Batch epilogue without disturbing state)."""
        if self._armed is not None:
            return self._armed.value()
        raise CodegenError("no armed function; use the SessionResult "
                           "returned by run()")

    # -- incremental updates -------------------------------------------------
    def apply(self, batch: UpdateBatch) -> "Session":
        """Feed one ΔG batch to the armed Batch loop (falling back to a
        structural update when nothing is armed).  On diff-pool overflow
        the state is rolled back, the pool grown, and the batch
        replayed — so ``capacity='auto'`` underestimates are repaired,
        not wrong."""
        if self._armed is None:
            super().apply(batch)
            return self
        if self._armed.returned:
            return self    # a batch body returned: the Batch loop is
                           # over, exactly as in a one-shot run
        admitted = self._guard.admit(batch, self._n_vertices(),
                                     cursor=self._cursor)
        if admitted is None:          # quarantined: batch consumed
            self._cursor += 1
            return self
        self._apply_armed(admitted)
        return self

    def _apply_armed(self, batch: UpdateBatch) -> None:
        """One already-admitted batch through the armed loop body, with
        snapshot rollback (overflow regrow-and-replay, and clean state
        for failover's migrate-and-replay on kernel failure)."""

        def op():
            armed = self._armed   # re-read: migration re-arms
            snap = [armed.snapshot()]

            def attempt():
                armed.apply(batch)
                self._handle = armed.gbox.value

            def regrow():
                armed.restore(snap[0])
                armed.gbox.value = self._engine.grow(armed.gbox.value)
                self._handle = armed.gbox.value
                snap[0] = armed.snapshot()

            def rollback():
                armed.restore(snap[0])
                self._handle = armed.gbox.value

            self._retry_on_overflow(attempt, regrow, batch=batch,
                                    rollback=rollback)

        self._guarded(op)
        self._props = self._armed.device_props()
        self._cursor += 1

    def _armed_stream_loop(self, stream: UpdateStream, bs: int) -> None:
        """Fold a stream through the armed loop with STREAM-level
        admission.  Batch-level inspection cannot see every violation —
        ``UpdateStream.batch()`` int-casts NaN weights and clamps them
        to >= 1 while padding — so poison batches are located on the raw
        host rows first and resolved per policy as the loop reaches
        them."""
        n = self._n_vertices()
        poison = self._guard.inspect_stream(stream, bs, n)
        if poison and self._guard.policy == "reject":
            first = min(poison)
            self._guard.resolve(stream.batch(first, bs), poison[first],
                                self._cursor, first, n)
            raise AssertionError("reject policy must raise")  # pragma: no cover
        for i in range(stream.num_batches(bs)):
            if self._armed.returned:
                break            # a batch body returned: stop, like the
            batch = stream.batch(i, bs)   # one-shot Batch loop does
            if i in poison:
                batch = self._guard.resolve(batch, poison[i],
                                            self._cursor, i, n)
                if batch is None:         # quarantined: batch consumed
                    self._cursor += 1
                    continue
            elif self._guard.policy != "off":
                self._health.admitted += 1
            self._apply_armed(batch)

    # -- failover ------------------------------------------------------------
    def _migrate(self, name: str) -> None:
        """Backend migration with the armed Batch loop carried across:
        the paused frame is serialized on the failing backend (pure data
        access — works even when its kernels don't), the graph state is
        converted through the canonical alive-edge list, and the frame
        is re-staged and deserialized on the survivor."""
        armed_state = None
        if self._armed is not None:
            arrays, armed_meta = self._armed.serialize()
            for pname, m in armed_meta["env"].items():
                if m["kind"] == "prop" and m.get("bound") and m["is_edge"]:
                    raise KernelFailure(
                        f"cannot fail over to {name!r}: armed edge "
                        f"property {pname!r} is bound to the "
                        f"{self._backend_name!r} pool layout",
                        backend=name)
            armed_state = ({k: np.asarray(v) for k, v in arrays.items()},
                           armed_meta)
        n = self._engine.n_real
        super()._migrate(name)
        # staged executables embed the old engine's jitted closures
        self._staged_funcs.clear()
        if armed_state is not None:
            self._rearm(armed_state[1], armed_state[0], n)

    def _rearm(self, armed_meta: dict, arrays: dict, n: int) -> None:
        """Re-stage an armed Batch loop on the CURRENT engine and
        rebuild its paused frame from serialized arrays (shared by
        failover migration and ``restore_session``)."""
        for pname, m in armed_meta["env"].items():
            if m["kind"] == "prop" and m.get("bound") and not m["is_edge"]:
                arrays[f"prop_{pname}"] = self._engine.put_vertex_array(
                    _fit_pad(arrays[f"prop_{pname}"], n,
                             self._engine.n_pad))
        staged = self._staged(armed_meta["func"])
        self._armed = ArmedRun.deserialize(staged, self._handle, arrays,
                                           armed_meta)
        self._handle = self._armed.gbox.value
        self._props = self._armed.device_props()

    # -- durability ----------------------------------------------------------
    def state_tree(self):
        """Adds the program identity and (when armed) the serialized
        Batch-loop position to the GraphSession snapshot."""
        tree, meta = super().state_tree()
        meta["kind"] = "session"
        meta["source"] = self.compiled.program.source
        if self._armed is not None:
            arrays, armed_meta = self._armed.serialize()
            tree["armed"] = arrays
            meta["armed"] = armed_meta
        return tree, meta

    def run_stream(self, stream: UpdateStream, batch_size: Optional[int] =
                   None, step_fn: Optional[Callable] = None, carry=None,
                   **kw):
        """Armed sessions: fold a whole update stream through the armed
        Batch loop, one ``apply`` per batch; returns a
        :class:`SessionResult`.  With an explicit ``step_fn`` this
        instead delegates to the engine's fused executor (the
        GraphSession/hand-staged path) and returns the final carry,
        not a SessionResult."""
        if step_fn is not None:
            out = super().run_stream(stream, batch_size, step_fn, carry,
                                     **kw)
            # successful hand-staged streaming supersedes any armed DSL
            # loop: the armed frame's graph box would otherwise go stale
            # and a later apply() would silently revert these updates
            self._armed = None
            return out
        if self._armed is None:
            raise CodegenError("run_stream without step_fn needs an armed "
                               "function; call run(func, ...) without its "
                               "updates argument first")
        if carry is not None or kw:
            raise TypeError(
                f"run_stream on an armed session takes only (stream, "
                f"batch_size); carry/{sorted(kw)} belong to the step_fn "
                f"(hand-staged) path")
        bs = batch_size
        if bs is None:
            # the batchSize the function was armed with, if any
            try:
                bs = self._armed.frame.lookup(
                    self._armed.batch_stmt.batch_size)
            except CodegenError:
                bs = None
        if bs is None:
            raise CodegenError("no batch size: pass run_stream(..., "
                               "batch_size=N) or batchSize= at arm time")
        self._ensure_prepared(stream=stream)
        self._armed_stream_loop(stream, int(bs))
        self._watch(self._props, where="run_stream(armed)")
        return SessionResult(self, self.props, value=self._armed.value())


class CompiledProgram:
    """A compiled DSL program, backend-agnostic; ``bind`` picks the
    backend by registry name and yields a :class:`Session`."""

    def __init__(self, program: Program):
        self.program = program

    @property
    def functions(self):
        """Names of the functions this program defines."""
        return [f.name for f in self.program.ast.funcs]

    def bind(self, csr: CSR, backend: str = "jnp",
             capacity: Union[str, int] = "auto",
             admission: str = "clamp",
             max_batch: int = DEFAULT_MAX_BATCH,
             dead_letter: int = 64,
             failover=None,
             **backend_opts) -> Session:
        """Bind to a graph on a named backend.  ``capacity`` sizes the
        diff-CSR pool: an int is explicit; ``"auto"`` derives it from
        the stream of the first one-shot run (armed sessions prepare
        for the prologue before any update exists, so they start at the
        default size), with grow-on-overflow as the backstop either
        way.

        Runtime knobs mirror :func:`bind_graph`: ``admission`` is the
        ΔG validation policy (``reject | clamp | quarantine | off``),
        ``failover=True`` enables the registry's degradation chain for
        ``backend`` (or pass an explicit tuple of fallback names) —
        including at bind time: if the preferred backend fails to
        construct, the session comes up degraded on the first survivor
        and re-probes the preferred backend on a backoff timer."""
        if failover:
            engine, bound = _make_engine_failover(backend, failover,
                                                  **backend_opts)
        else:
            engine, bound = make_engine(backend, **backend_opts), backend
        sess = Session(self, engine, csr, capacity,
                       backend_name=bound, admission=admission,
                       max_batch=max_batch, dead_letter=dead_letter,
                       failover=failover)
        _post_bind_failover(sess, backend, bound, failover)
        return sess

    def __repr__(self):
        return f"CompiledProgram(functions={self.functions})"


def restore_session(ckpt_dir, backend: Optional[str] = None,
                    step: Optional[int] = None,
                    engine: Optional[Engine] = None,
                    **backend_opts) -> GraphSession:
    """Reconstruct a session from a checkpoint directory written by
    ``Session.save`` / ``GraphSession.save``.

    ``step=None`` picks the latest committed step.  ``backend=None``
    restores onto the backend that saved:

    * same backend kind — **bit-exact**: the raw handle leaves (diff
      pool, tombstones, ELL pack) are restored, so resumed streaming is
      bit-identical to the uninterrupted run;
    * the dist backends (``dist`` / ``dist_sharded``) re-partition
      their canonical edge list onto the *current* mesh — an elastic
      restore may come back on a different device count
      (``restore_session(dir, num_shards=M)``) or, for the sharded
      backend, a different row partitioner (value-exact for
      order-independent reductions);
    * naming a **different** backend converts through the canonical
      alive-edge list and re-``prepare``s (value-preserving, pool
      layout reset).

    An armed Batch loop resumes exactly where it paused; the prologue is
    not re-run.  The result is a :class:`Session` when the checkpoint
    was written by one (program source travels in the manifest),
    otherwise a :class:`GraphSession`.

    ``engine=`` restores onto an ALREADY-CONSTRUCTED engine instance
    instead of building a fresh one (mutually exclusive with
    ``backend``/``backend_opts``).  The serving pool revives evicted
    sessions this way so they rejoin the pool's shared-executable
    engine — a fresh engine would recompile everything and break the
    pool's batching groups.  Bit-exactness then requires the instance's
    ``state_kind`` to match the saver's, same as a name-based restore.
    """
    if step is None:
        step = ckpt.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(
                f"no committed checkpoint under {ckpt_dir}")
    meta = ckpt.read_manifest(ckpt_dir, step)["extra"]
    if engine is not None:
        if backend is not None or backend_opts:
            raise ValueError("restore_session: pass either engine= or "
                             "backend=/**backend_opts, not both")
    else:
        engine = make_engine(backend or meta["backend"], **backend_opts)
    example = _example_from_spec(meta["tree_spec"])
    tree, _ = ckpt.restore(ckpt_dir, step, example)
    # strip the restore's single-device commitment: the engine re-places
    # every leaf (dist shards vertex arrays over its own mesh)
    tree = jax.tree_util.tree_map(np.asarray, tree)

    hmeta = meta["handle"]
    exact = engine.state_kind == hmeta["kind"]
    if exact:
        handle = engine.unpack_state(tree["handle"], hmeta)
    else:
        csr, cap = state_to_csr(tree["handle"], hmeta)
        handle = engine.prepare(csr, diff_capacity=cap)
    # edge-LANE state only survives when the pool layout does: a dist
    # restore re-partitions even same-kind, invalidating lane indices
    lanes_ok = exact and hmeta["kind"] != "dist"

    if meta["kind"] == "session":
        sess: GraphSession = Session(compile(meta["source"]), engine,
                                     csr=None)
    else:
        sess = GraphSession(engine, csr=None)
    sess._handle = handle
    n = int(meta["n"])
    sess._props = {k: engine.put_vertex_array(_fit_pad(v, n, engine.n_pad))
                   for k, v in tree.get("props", {}).items()}
    sess._cursor = int(meta["cursor"])

    armed_meta = meta.get("armed")
    if armed_meta is not None:
        arrays = dict(tree.get("armed") or {})
        for name, m in armed_meta["env"].items():
            if m["kind"] == "prop" and m.get("bound") and m["is_edge"] \
                    and not lanes_ok:
                raise ValueError(
                    f"armed edge property {name!r} is bound to the "
                    f"saved pool layout; it cannot survive a "
                    f"cross-backend restore or a dist re-mesh — "
                    f"restore onto the saving backend, or disarm "
                    f"before saving")
        sess._rearm(armed_meta, arrays, n)
    # one triple read pins the overflow base for the restored pool
    sess._of_base = sess._sync_counters()[0]
    return sess
