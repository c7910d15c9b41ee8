"""The three servers of Figure 1a.

Workers communicate EXCLUSIVELY through these: a data buffer server and
two parameter servers (model, policy). Thread-safe, versioned; ``pull``
never blocks on a writer (the paper's lock-free spirit at phase
granularity — see DESIGN.md §2 for the TPU adaptation).

Three transport families share one interface — the :class:`ParameterTransport`
/ :class:`DataTransport` protocols below (PR 9), so workers, engines, and
supervisors are transport-blind:

* in-process (``ParameterServer`` / ``DataServer``): device-resident,
  zero-copy — the event and threads engines;
* cross-process (``ShmParameterServer`` / ``ProcDataServer``): the
  ``mode="procs"`` engine. Parameters live in a posix shared-memory
  segment serialised with the flat-key codec from ``checkpoint/io.py``
  (never pickled per-pull); trajectories ride a ``multiprocessing``
  queue into the model worker's ring buffer. The PR 1 version contract
  is preserved: ``push`` bumps an atomic version, ``pull_if_newer`` on
  an unchanged version is ONE 8-byte read — zero array copies
  (counter-instrumented; asserted by tests/test_procs.py);
* cross-host (``repro.net``: ``TcpParameterServer`` / ``TcpDataServer``
  against a ``ControlPlane``): ``RunConfig.transport="tcp"``. The
  version word rides the 32-byte frame header, so an unchanged
  ``pull_if_newer`` moves ZERO array bytes over the wire; the ticket
  counters live on the plane, so the exact criterion and crash-refund
  semantics hold verbatim across hosts. See docs/WIRE_PROTOCOL.md.

Both data servers are MULTI-PRODUCER (collector fleets, ISSUE 5): N
collectors push concurrently, the global trajectory counter stays exact
under interleaved pushes and collector restarts, and the stopping
criterion is ticket-based (``try_claim``) so a fleet can never overshoot
``total_trajs``. The model worker's drain batches a burst of M
trajectories into ONE compile-once padded scatter
(``ReplayBuffer.add_trajs``) instead of M sequential ring writes.

Hot-path invariants (see benchmarks/hotpath.py, which enforces them):

* ``ParameterServer`` keeps values DEVICE-RESIDENT. ``push``/``pull``
  never round-trip through the host; ``pull_host`` exists only for
  checkpoint / serving boundaries.
* ``ParameterServer.pull_if_newer(version)`` costs one lock + integer
  compare when the version is unchanged — no pytree traversal, no copy.
* ``ReplayBuffer`` is a preallocated fixed-capacity ring of static-shape
  arrays: no per-epoch ``np.concatenate``, no growing shapes, so a
  trainer compiled against ``train_view()`` never retraces.
"""
from __future__ import annotations

import queue as _queue
import struct
import threading
import time
from typing import (Any, Dict, List, Optional, Protocol, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

# NOTE: on backends without buffer aliasing (CPU) the donated jits below
# warn once at compile that donation fell back to a copy — that is
# expected there and left visible on purpose (no global warning filter).


# ------------------------------------------------------------ transport seam
#
# The PR 9 pluggable-transport contract. These protocols are DOCUMENTED
# interfaces, not base classes: the three implementations (in-process,
# shm/mp, tcp) share no code — each earns the guarantees its own way —
# and `isinstance(x, ParameterTransport)` checks the seam structurally.

@runtime_checkable
class ParameterTransport(Protocol):
    """What every parameter store guarantees, whatever the wire.

    * ``push(value) -> version``: publish atomically; a reader can never
      observe a torn value (device snapshot / seqlock / server-side swap
      under one lock). Monotone: each push bumps the version by 1.
    * ``pull_if_newer(version, *, sharding=None) -> (value|None, ver)``:
      the UNCHANGED path transfers no array data — one int compare
      (in-process), one 8-byte shm read, or one header-only TCP
      round-trip — and is counter-asserted by tests and benchmarks.
    * ``pull() -> (value|None, version)``: unconditional latest.
    * ``pull_host() -> (host value|None, version)``: the only sanctioned
      device->host boundary (checkpoint / serving / supervisor).
    * ``version -> int``: current version; 0 means nothing pushed yet.
    * crash safety: a writer dying mid-push never corrupts what readers
      see — they keep their cached value (degrade, never hang or tear).
    """

    def push(self, value) -> int: ...
    def pull(self): ...
    def pull_if_newer(self, version: int, *, sharding=None): ...
    def pull_host(self): ...
    @property
    def version(self) -> int: ...


@runtime_checkable
class DataTransport(Protocol):
    """What every trajectory data server guarantees, whatever the wire.

    * ``push(traj, *, collector_id)`` / ``push_batch(batch, n, *,
      collector_id)``: multi-producer safe; ``total_pushed`` moves
      atomically with the pusher's in-flight settlement (one lock), so
      the global count is exact under interleaving and restarts.
    * ``try_claim(collector_id, k) -> granted``: reserves
      ``min(k, remaining)`` toward the armed target under that same
      lock — a fleet can never overshoot; denied claims back off
      ``claim_backoff`` seconds instead of spinning.
    * ``refund_inflight(collector_id) -> n``: returns EXACTLY the
      tickets claimed-but-never-pushed by a dead collector; idempotent.
    * ``drain() -> [traj dict, ...]``: moves everything queued to the
      caller; batch items are unstacked into per-lane dicts.
    * ``set_target(total)`` arms the criterion; ``total_pushed`` /
      ``__len__`` report exact global progress.
    * backpressure: a push against a full bounded queue raises
      :class:`BackpressureError` after ``push_timeout`` — loud, never a
      silent drop (the unbounded in-process server never blocks).
    """

    def push(self, traj, *, collector_id: int = 0) -> int: ...
    def push_batch(self, batch, n: int, *, collector_id: int = 0) -> int: ...
    def set_target(self, total: int) -> None: ...
    def try_claim(self, collector_id: int = 0, k: int = 1) -> int: ...
    def refund_inflight(self, collector_id: int) -> int: ...
    def drain(self) -> List[Any]: ...
    @property
    def total_pushed(self) -> int: ...
    def __len__(self) -> int: ...


class ParameterServer:
    """Versioned pytree store (Alg. 1/2/3 'Pull/Push parameters').

    Values stay on device. ``push`` snapshots leaves with a device-side
    copy so published versions are isolated from training buffers that
    the pusher later donates back into its jitted update step.

    Placement-aware (role meshes, core/roles.py): ``push`` records the
    source sharding; ``pull_if_newer(version, sharding=...)`` moves the
    value onto the puller's sub-mesh with an explicit device-to-device
    ``device_put`` — only on a version change, and only when the source
    placement differs. The unchanged path stays one lock + int compare.
    """

    def __init__(self, initial=None):
        self._lock = threading.Lock()
        # snapshot like push(): the stored version must stay isolated
        # from buffers the caller may later donate into a jit
        self._value = None if initial is None else self._snapshot(initial)
        self._version = 0 if initial is None else 1
        self._src_sharding = (None if self._value is None
                              else self._leaf_sharding(self._value))

    @staticmethod
    def _snapshot(value):
        # device->device copy (cheap); NOT a host transfer. Isolates the
        # stored version from donate_argnums buffer reuse by the pusher.
        return jax.tree.map(jnp.copy, value)

    @staticmethod
    def _leaf_sharding(value):
        """Sharding of the pushed pytree (first jax leaf; one pytree holds
        one role's params, so leaves share a placement)."""
        for leaf in jax.tree.leaves(value):
            s = getattr(leaf, "sharding", None)
            if s is not None:
                return s
        return None

    def push(self, value) -> int:
        with TraceAnnotation("param.push"):
            snap = self._snapshot(value)    # copy outside the lock
            src = self._leaf_sharding(snap)
            with self._lock:
                self._value = snap
                self._src_sharding = src
                self._version += 1
                return self._version

    def pull(self):
        """Returns (value, version); value is None until the first push."""
        with self._lock:
            return self._value, self._version

    def pull_if_newer(self, version: int, *, sharding=None):
        """Version-gated pull: returns (value, current_version) when the
        server holds something newer than ``version``, else
        (None, current_version). The unchanged path is one lock + int
        compare — no copies, no pytree traversal (and therefore no
        transfer of any kind: it passes jax.transfer_guard('disallow')).

        ``sharding``: the puller's target placement (e.g. params
        replicated over its role sub-mesh). Applied only on a version
        change, and skipped when the pusher already produced that
        placement — cross-role movement is a device-to-device
        ``device_put``, never a host round-trip."""
        with self._lock:
            if self._version == version or self._value is None:
                return None, self._version
            value, ver, src = self._value, self._version, self._src_sharding
        with TraceAnnotation("param.pull"):
            if sharding is not None and src != sharding:
                # outside the lock: value is an immutable snapshot; one
                # pytree-aware device_put batches all leaf transfers
                value = jax.device_put(value, sharding)
            return value, ver

    def pull_host(self):
        """Host-materialised pull for checkpoint / serving boundaries —
        the ONLY place a device->host copy of the store is allowed."""
        with self._lock:
            value, version = self._value, self._version
        if value is None:
            return None, version
        return jax.tree.map(np.asarray, value), version

    @property
    def version(self) -> int:
        with self._lock:
            return self._version


class DataServer:
    """FIFO trajectory buffer server (Alg. 1 'Push data', Alg. 2 line 3:
    'move all trajectories from the remote buffer').

    Explicitly MULTI-PRODUCER (collector fleets, ISSUE 5): any number of
    collectors push concurrently; one lock makes ``total_pushed`` exact
    under interleaved pushes. The global stopping criterion is enforced
    with a ticket counter: ``set_target(n)`` arms it and ``try_claim(k)``
    hands out at most ``n - total_pushed_at_arm_time`` collection slots,
    so a fleet finishes with ``total_pushed == n`` EXACTLY — never an
    overshoot from two collectors racing past the threshold. Batch-aware
    (env farms, ISSUE 6): ``try_claim(k=B)`` grants 0..B tickets under
    the one lock — ``min(B, remaining)`` — so a farm's last batch shrinks
    to land the criterion exactly; a denied claim sleeps
    ``claim_backoff`` seconds before returning so collectors that lose
    the race near the criterion don't spin-poll at full speed.

    Zero-copy: pushed trajectories are stored by reference (jax arrays
    are immutable, so handing them across threads is safe) — no
    device->host materialisation on the hot path; a pushed BATCH is
    unstacked into per-lane slices (lazy jax views, no copies)."""

    def __init__(self, *, claim_backoff: float = 0.002):
        self.claim_backoff = float(claim_backoff)
        self._lock = threading.Lock()
        self._items: List[Any] = []
        self._total = 0
        self._target: Optional[int] = None
        self._tickets = 0
        self._inflight: Dict[int, int] = {}

    def push(self, traj, *, collector_id: int = 0) -> int:
        with TraceAnnotation("data.push"), self._lock:
            self._items.append(traj)
            self._total += 1
            self._dec_inflight(collector_id, 1)
            return self._total

    def push_batch(self, batch, n: int, *, collector_id: int = 0) -> int:
        """Push ``n`` trajectories stacked as one batch (dict of
        (n, H, ...) arrays — a farm step's output). Consumers always see
        per-trajectory dicts: the batch is unstacked into lane slices
        OUTSIDE the lock, then appended and counted atomically, so
        ``total_pushed`` moves by n in one step and interleaved
        producers stay exact."""
        with TraceAnnotation("data.push"):
            lanes = [{k: v[i] for k, v in batch.items()} for i in range(n)]
            with self._lock:
                self._items.extend(lanes)
                self._total += n
                self._dec_inflight(collector_id, n)
                return self._total

    def set_target(self, total: int) -> None:
        """Arm the stopping criterion: from now on ``try_claim`` grants
        exactly ``total - total_pushed`` more collection slots."""
        with self._lock:
            self._target = int(total)
            self._tickets = self._total

    def try_claim(self, collector_id: int = 0, k: int = 1) -> int:
        """Reserve up to ``k`` collection slots toward the armed target;
        marks them in-flight for ``collector_id`` until the matching
        push lands. Returns the number granted — ``min(k, remaining)``,
        possibly 0 once the target is fully claimed (the collector
        should stop). No target configured: always grants ``k``. The
        denied path sleeps ``claim_backoff`` (outside the lock) so
        losers of the final-claim race back off instead of spinning."""
        k = int(k)
        with self._lock:
            g = k if self._target is None else \
                min(k, max(self._target - self._tickets, 0))
            if g > 0:
                self._tickets += g
                self._inflight[collector_id] = \
                    self._inflight.get(collector_id, 0) + g
                return g
        time.sleep(self.claim_backoff)
        return 0

    def refund_inflight(self, collector_id: int) -> int:
        """Return every ticket ``collector_id`` claimed but never
        pushed (its collector died mid-batch). Returns the number
        refunded. Mirror of :meth:`ProcDataServer.refund_inflight` for
        supervisors of in-process fleets."""
        with self._lock:
            g = self._inflight.pop(collector_id, 0)
            self._tickets -= g
            return g

    def _dec_inflight(self, collector_id: int, n: int) -> None:
        # already holding self._lock. Claims are optional (the event
        # engine pushes without claiming), so clamp at zero.
        left = self._inflight.get(collector_id, 0) - n
        if left > 0:
            self._inflight[collector_id] = left
        else:
            self._inflight.pop(collector_id, None)

    def drain(self) -> List[Any]:
        """Move ALL pending trajectories to the caller (empties server)."""
        with self._lock:
            items, self._items = self._items, []
            return items

    @property
    def total_pushed(self) -> int:
        with self._lock:
            return self._total

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


# ----------------------------------------------------------------- procs IPC
#
# Cross-process equivalents for mode="procs" (runtime._run_procs). The
# parent creates them before spawning workers; the handles are picklable
# through multiprocessing's spawn machinery and re-attach lazily in each
# child. See ROADMAP.md "Process-isolation invariants (PR 4)".

_SHM_HEADER = 64            # [0:8) seqlock, [8:16) version, rest reserved
_SHM_ALIGN = 64             # leaf payloads start cache-line aligned

# ---- auditable lifetime registries (chaos/soak, PR 7) ----------------
# Every IPC resource this PROCESS creates (shm segments it owns, data
# servers it constructed) is registered at birth and unregistered by
# close(), so a resource auditor can prove "zero leaks" by asserting the
# registries are empty after shutdown — and a supervisor's last-resort
# cleanup can reclaim stragglers without knowing who made them.
_REGISTRY_LOCK = threading.Lock()
_SHM_REGISTRY: Dict[str, "ShmParameterServer"] = {}
_DATA_REGISTRY: Dict[int, "ProcDataServer"] = {}


def live_shm_segments() -> Tuple[str, ...]:
    """Names of posix shm segments created by this process and not yet
    closed/unlinked. Empty after every clean or chaotic shutdown."""
    with _REGISTRY_LOCK:
        return tuple(sorted(_SHM_REGISTRY))


def live_data_servers() -> int:
    """Count of ProcDataServers constructed by this process whose
    ``close()`` has not run yet."""
    with _REGISTRY_LOCK:
        return len(_DATA_REGISTRY)


def reclaim_ipc_resources() -> int:
    """Guaranteed-reclaim path: close every still-registered shm segment
    and data server created by this process. Returns how many resources
    were reclaimed. Safe to call repeatedly; normal shutdown (context
    managers / runtime ExitStack) leaves nothing for it to do."""
    with _REGISTRY_LOCK:
        stragglers = list(_SHM_REGISTRY.values()) + \
            list(_DATA_REGISTRY.values())
    for res in stragglers:
        try:
            res.close()
        except Exception:
            pass
    return len(stragglers)


def _attach_shm(name):
    """Attach (never create) an existing segment WITHOUT handing its
    lifetime to this process's resource tracker.

    Python < 3.13 registers POSIX shm with the tracker on ATTACH too
    (bpo-39959): harmless for mp-spawned workers (they inherit the
    creator's tracker, whose bookkeeping the creator's ``unlink``
    balances), but a standalone attacher — e.g. a tool unpickling a
    server handle — starts its OWN tracker, which would unlink the live
    segment when that process exits. So: prefer ``track=False``
    (3.13+); otherwise unregister ONLY when the attach just started a
    fresh tracker, i.e. this process is a standalone attacher (an
    inherited-tracker unregister would instead erase the creator's
    registration and spray KeyErrors at unlink time)."""
    from multiprocessing import shared_memory
    try:
        return shared_memory.SharedMemory(name=name, track=False)
    except TypeError:
        pass
    try:
        from multiprocessing import resource_tracker
        had_tracker = getattr(resource_tracker._resource_tracker,
                              "_fd", None) is not None
    except Exception:
        had_tracker = True      # can't tell: don't touch the tracker
    shm = shared_memory.SharedMemory(name=name)
    if not had_tracker:
        try:
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
    return shm


class ShmParameterServer:
    """Versioned parameter store in ONE posix shared-memory segment.

    The pytree structure is FIXED at construction from a template (the
    worker's initial params): leaves are serialised with the flat-key
    codec from ``checkpoint/io.py`` into preallocated aligned slots —
    a push is a plain ``memcpy`` per leaf, never a pickle.

    Concurrency is a single-writer seqlock (each server is written by
    exactly one role — model worker or policy worker):

    * ``push``: bump the sequence word to odd, copy payload, bump to
      even, then bump the version word (one atomic aligned 8-byte
      store). Version therefore never points at a torn payload.
    * ``pull_if_newer(version)``: ONE 8-byte read when unchanged — zero
      array copies, no lock to block on (``copies`` counts every leaf
      copied out; the unchanged path leaves it untouched). On a version
      change the payload is copied out inside a stable even-sequence
      window, retrying while a writer overlaps.
    * crash safety: a writer killed mid-push leaves the sequence odd;
      readers simply keep their cached value (degrade, not hang) and
      the restarted writer's next push re-synchronises the sequence.
      No cross-process lock exists, so there is nothing to repair.

    Benign race: version is bumped after the payload settles, so a
    reader can momentarily get a fresher payload with the previous
    version number — the next gated pull re-copies; never torn data.
    """

    _READ_RETRIES = 64

    def __init__(self, template):
        from multiprocessing import shared_memory

        from repro.checkpoint.io import LeafCodec
        self._codec = LeafCodec(template)
        self._offsets = []
        off = _SHM_HEADER
        for n in self._codec.nbytes:
            self._offsets.append(off)
            off += max(int(n), 1)
            off += (-off) % _SHM_ALIGN
        self._size = off
        shm = shared_memory.SharedMemory(create=True, size=self._size)
        self._name = shm.name
        self._shm = shm
        self._owner = True          # creator unlinks; children only close
        self._views = None
        shm.buf[:_SHM_HEADER] = b"\0" * _SHM_HEADER
        self.copies = 0             # client-local: leaves copied OUT
        self.pushes = 0             # client-local: pushes issued
        with _REGISTRY_LOCK:        # auditable lifetime (creator only)
            _SHM_REGISTRY[self._name] = self

    # -- pickling: children re-attach to the named segment lazily -------
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_shm"] = None
        state["_views"] = None
        state["_owner"] = False
        return state

    def _seg(self):
        if self._shm is None:
            self._shm = _attach_shm(self._name)
        return self._shm

    def _leaf_views(self):
        if self._views is None:
            buf = self._seg().buf
            self._views = [
                np.frombuffer(buf, dtype=sd,
                              count=int(np.prod(sh, dtype=np.int64)),
                              offset=off).reshape(sh)
                for sd, sh, off in zip(self._codec.storable_dtypes,
                                       self._codec.shapes, self._offsets)]
        return self._views

    def _read_word(self, off) -> int:
        return struct.unpack_from("<q", self._seg().buf, off)[0]

    def _write_word(self, off, value) -> None:
        struct.pack_into("<q", self._seg().buf, off, value)

    def push(self, value) -> int:
        host = self._codec.encode(value)    # the one device->host hop
        views = self._leaf_views()
        seq = self._read_word(0)
        begin = seq + 1 + (seq % 2)         # next odd > seq, even if a
        self._write_word(0, begin)          # crashed writer left it odd
        for view, arr in zip(views, host):
            np.copyto(view, arr, casting="no")
        self._write_word(0, begin + 1)      # payload settled (even)
        ver = self._read_word(8) + 1        # single writer: RMW is safe
        self._write_word(8, ver)
        self.pushes += 1
        return ver

    def pull_if_newer(self, version: int, *, sharding=None):
        """(value, current_version) when newer than ``version`` else
        (None, version-as-seen). Unchanged cost: ONE aligned 8-byte read.
        ``sharding`` is accepted for interface parity with
        :class:`ParameterServer` and ignored: pulled leaves are host
        arrays — the worker re-homes them onto its own device/backend
        (each process owns a separate jax runtime)."""
        ver = self._read_word(8)
        if ver == version or ver == 0:
            return None, ver
        views = self._leaf_views()
        for _ in range(self._READ_RETRIES):
            s1 = self._read_word(0)
            if s1 % 2:                      # writer mid-copy
                time.sleep(0.0005)
                continue
            out = [np.array(v) for v in views]
            if self._read_word(0) == s1:    # no writer overlapped
                self.copies += len(out)
                # return the version read at ENTRY, not a re-read: the
                # payload is at least that fresh, and labelling it with
                # a version that completed during the copy would let
                # the next gated pull skip a push the caller never saw.
                # Worst case here is one redundant re-copy.
                return self._codec.decode(out), ver
        # writer crashed mid-push (sequence stuck odd) or pathological
        # contention: degrade — caller keeps its cache and retries later
        return None, version

    def pull(self):
        value, ver = self.pull_if_newer(-1)
        return value, (ver if value is not None else self.version)

    def pull_host(self):
        """Interface parity with ParameterServer: pulls are already
        host-materialised."""
        return self.pull()

    @property
    def version(self) -> int:
        return self._read_word(8)

    # -- lifecycle ------------------------------------------------------
    def close(self) -> None:
        """Drop this process's mapping (and unlink if creator).
        Idempotent; the creator's close also clears the audit registry
        entry, so ``live_shm_segments()`` proves reclamation."""
        self._views = None          # np views pin shm.buf; drop them first
        if self._shm is not None:
            self._shm.close()
            if self._owner:
                try:
                    self._shm.unlink()
                except FileNotFoundError:
                    pass
            self._shm = None
        if self._owner:
            with _REGISTRY_LOCK:
                _SHM_REGISTRY.pop(self._name, None)

    def __enter__(self) -> "ShmParameterServer":
        return self

    def __exit__(self, *exc) -> None:
        # teardown must not depend on GC order: runtime._run_procs holds
        # every server in one ExitStack so ALL exit paths reclaim
        self.close()


class BackpressureError(RuntimeError):
    """A ``ProcDataServer.push`` timed out on a full trajectory queue —
    the consumer (the model worker's drain -> ring-write path) is not
    keeping up with the collector fleet."""


class ProcDataServer:
    """Cross-process DataServer: a bounded trajectory queue. Collectors
    push host-materialised trajectories; the model worker drains them
    into its ring ReplayBuffer (Alg. 2 'move all trajectories from the
    remote buffer').

    Explicitly MULTI-PRODUCER (collector fleets, ISSUE 5): ``total_pushed``
    and the stopping-criterion tickets live behind ONE shared lock, so the
    global trajectory count stays exact under concurrent pushes from any
    number of collector processes AND across collector crash/restarts (a
    restarted collector resumes the global count instead of re-collecting
    from zero). ``try_claim(i, k)`` reserves up to ``k`` collection slots
    — ``min(k, remaining)``, batch-aware for env farms (ISSUE 6) — and
    adds them to collector ``i``'s in-flight COUNT; ``push`` /
    ``push_batch`` subtract what they deliver. A collector killed
    mid-batch leaves its undelivered tickets in flight — the supervising
    parent calls ``refund_inflight(i)`` when it respawns the worker and
    gets back exactly the stranded count, so a crash can never strand a
    ticket (stall) or push the COUNTER past the target (overshoot). A
    denied claim sleeps ``claim_backoff`` seconds before returning, so
    collectors that lose the race near the criterion back off instead of
    spin-polling. One documented residual window: a kill between the
    queue enqueue and the counter increment leaves refundable tickets
    whose trajectories already landed in the queue, so the replacement's
    pushes put EXTRA trajectories in the training stream —
    ``total_pushed`` (the stopping criterion) stays exact, the model
    just trains on a few extra trajectories. Closing it would need a
    transactional queue; the window is microseconds inside ``push``. A
    second residual window, inherited from the PR 4 counter: the ticket
    lock (and the mp.Queue's internal writer lock) is a plain
    non-robust mp lock, so a kill while one is held — a few counter
    updates, or a feeder-thread pipe write — leaves it held and stalls
    the other collectors. That failure is LOUD, not silent: stalled
    pushes hit ``push_timeout`` and raise :class:`BackpressureError`,
    the crashing collectors burn ``max_restarts`` and the parent fails
    the run. The shm parameter path stays deliberately lock-free (see
    ShmParameterServer).

    Backpressure: a push against a full queue waits ``push_timeout``
    seconds, then raises :class:`BackpressureError` naming the queue size
    and the slowest consumer instead of surfacing a bare ``queue.Full``.
    The timeout is a constructor argument threaded from
    ``RunConfig.push_timeout_s``."""

    def __init__(self, ctx, *, n_collectors: int = 1, maxsize: int = 512,
                 push_timeout: float = 30.0, target: Optional[int] = None,
                 claim_backoff: float = 0.002):
        self.n_collectors = max(int(n_collectors), 1)
        self.maxsize = int(maxsize)
        self.push_timeout = float(push_timeout)
        self.claim_backoff = float(claim_backoff)
        self._target = None if target is None else int(target)
        self._q = ctx.Queue(maxsize)
        # one lock guards ALL counters: total / tickets / in-flight
        # counts must move together for the criterion to be exact under
        # concurrent producers and supervisor refunds
        self._lock = ctx.Lock()
        self._total = ctx.Value("q", 0, lock=False)
        self._tickets = ctx.Value("q", 0, lock=False)
        self._inflight = ctx.Array("q", self.n_collectors, lock=False)
        self._closed = False
        self._creator = True        # children unpickle; only the creator
        with _REGISTRY_LOCK:        # process registers for the audit
            _DATA_REGISTRY[id(self)] = self

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_creator"] = False   # a child's copy is not auditable here
        return state

    def _raise_backpressure(self, collector_id, timeout):
        raise BackpressureError(
            f"trajectory queue full: collector {collector_id} waited "
            f"{timeout:.1f}s to push and the queue still holds "
            f"{self.maxsize} (maxsize) undrained items. The slowest "
            "consumer is the model worker's drain->ring-write path "
            "(ModelLearningWorker._refresh_data); raise "
            "RunConfig.push_timeout_s, enlarge the queue, or check "
            "whether the model process is wedged/compiling."
        ) from None

    def push(self, traj, *, collector_id: int = 0,
             timeout: Optional[float] = None) -> int:
        host = jax.tree.map(np.asarray, traj)   # process boundary
        timeout = self.push_timeout if timeout is None else timeout
        try:
            self._q.put(host, timeout=timeout)
        except _queue.Full:
            self._raise_backpressure(collector_id, timeout)
        with self._lock:
            self._total.value += 1
            self._settle_inflight(collector_id, 1)
            return self._total.value

    def push_batch(self, batch, n: int, *, collector_id: int = 0,
                   timeout: Optional[float] = None) -> int:
        """Push ``n`` trajectories stacked as one batch (dict of
        (n, H, ...) arrays — a farm step's output). The whole batch is
        host-materialised once and rides the queue as ONE item (a farm
        at B=256 would otherwise blow through ``maxsize`` per step);
        ``drain`` unstacks it into per-trajectory dicts of zero-copy np
        views on the consumer side."""
        host = jax.tree.map(np.asarray, batch)  # process boundary
        timeout = self.push_timeout if timeout is None else timeout
        try:
            self._q.put(("batch", int(n), host), timeout=timeout)
        except _queue.Full:
            self._raise_backpressure(collector_id, timeout)
        with self._lock:
            self._total.value += int(n)
            self._settle_inflight(collector_id, int(n))
            return self._total.value

    def _settle_inflight(self, collector_id: int, n: int) -> None:
        # already holding self._lock. Claims are optional (pushes may
        # arrive unclaimed before a target is armed), so clamp at zero.
        i = collector_id % self.n_collectors
        self._inflight[i] = max(int(self._inflight[i]) - n, 0)

    def try_claim(self, collector_id: int = 0, k: int = 1) -> int:
        """Reserve up to ``k`` collection slots toward the global
        target; adds the grant to the collector's in-flight count until
        its pushes land. Returns ``min(k, remaining)`` — 0 once the
        target is fully claimed (no target configured: always ``k``).
        The denied path sleeps ``claim_backoff`` outside the lock so
        losers of the final-claim race back off instead of spinning."""
        k = int(k)
        with self._lock:
            g = k if self._target is None else \
                min(k, max(self._target - self._tickets.value, 0))
            if g > 0:
                self._tickets.value += g
                self._inflight[collector_id % self.n_collectors] += g
                return g
        time.sleep(self.claim_backoff)
        return 0

    def refund_inflight(self, collector_id: int) -> int:
        """Supervisor hook: return every ticket of a collector that died
        between claim and push (its in-flight count is still positive).
        Called by the parent when respawning collector ``collector_id``;
        returns the number of tickets refunded — a farm collector
        SIGKILLed mid-batch gets its WHOLE undelivered remainder back,
        so the criterion can still land exactly."""
        with self._lock:
            i = collector_id % self.n_collectors
            g = int(self._inflight[i])
            if g > 0:
                self._inflight[i] = 0
                self._tickets.value -= g
            return g

    def drain(self) -> List[Any]:
        """Move everything queued to the caller as a flat list of
        per-trajectory dicts; batch items are unstacked into zero-copy
        np views along their lane axis."""
        items: List[Any] = []
        while True:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                return items
            if isinstance(item, tuple) and len(item) == 3 \
                    and item[0] == "batch":
                _, n, batch = item
                items.extend({k: v[i] for k, v in batch.items()}
                             for i in range(n))
            else:
                items.append(item)

    @property
    def total_pushed(self) -> int:
        with self._lock:
            return int(self._total.value)

    def __len__(self) -> int:
        try:
            return self._q.qsize()
        except NotImplementedError:     # macOS
            return 0

    def close(self) -> None:
        """Release this process's queue endpoint (feeder thread + pipe
        fds). Idempotent; the shared counters stay readable afterwards
        (``total_pushed`` still works for post-run reporting). The
        creator's close clears its audit-registry entry."""
        if self._closed:
            return
        self._closed = True
        self._q.close()
        self._q.join_thread()
        if self._creator:
            with _REGISTRY_LOCK:
                _DATA_REGISTRY.pop(id(self), None)

    def __enter__(self) -> "ProcDataServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# --------------------------------------------------------------------- ring
def _ring_write_impl(storage, traj, cursor):
    """Scatter one trajectory into the ring at ``cursor`` (wraps)."""
    h = jax.tree.leaves(traj)[0].shape[0]
    cap = jax.tree.leaves(storage)[0].shape[0]
    idx = (cursor + jnp.arange(h)) % cap
    return jax.tree.map(lambda buf, t: buf.at[idx].set(t), storage, traj)


_ring_write = jax.jit(_ring_write_impl, donate_argnums=(0,))


def _ring_write_burst_impl(storage, burst, n_rows, cursor):
    """Scatter a PADDED burst of stacked trajectories in ONE compiled
    write (collector fleets, ISSUE 5): ``burst`` leaves are
    ``(B, H, ...)`` stacks of which only the first ``n_rows`` flattened
    transitions (= M * H for M real trajectories) are valid. Padding
    rows are routed to index ``capacity`` — out of bounds — and DROPPED
    by the scatter (``mode="drop"``), so the shapes are static: one
    compile covers every burst size up to B, and a fleet's drain lands
    as one scatter instead of M sequential ring writes."""
    cap = jax.tree.leaves(storage)[0].shape[0]
    flat = jax.tree.map(
        lambda t: t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:]),
        burst)
    rows = jax.tree.leaves(flat)[0].shape[0]
    r = jnp.arange(rows)
    idx = jnp.where(r < n_rows, (cursor + r) % cap, cap)
    return jax.tree.map(
        lambda buf, t: buf.at[idx].set(t, mode="drop"), storage, flat)


_ring_write_burst = jax.jit(_ring_write_burst_impl, donate_argnums=(0,))


class ReplayBuffer:
    """Preallocated fixed-capacity transition ring with a held-out
    validation ring (Alg. 2: the model learner trains on its LOCAL
    buffer; §4 'The local buffer is of fixed size and first-in-first-out').

    Replaces ``LocalBuffer``'s list-of-trajectories + per-epoch
    ``np.concatenate``: storage is device-resident, shapes are static, the
    write is a single compiled scatter, and FIFO eviction falls out of the
    ring cursor. ``train_view``/``val_view`` return the full-capacity
    arrays plus the count of valid rows — consumers sample/mask against
    that count, so their compiled shapes never change as data accumulates.

    ``sharding`` (role meshes, core/roles.py): a ``NamedSharding`` that
    shards the transition (leading) axis over the owning worker's
    sub-mesh. Storage is allocated PRE-SHARDED, incoming trajectories are
    replicated onto the sub-mesh before the scatter, and the ring write is
    compiled once with the storage's own ``out_shardings`` — so
    ``_ring_write`` and any trainer fed from ``train_view`` stay
    compile-once exactly as on a single device. Capacities are rounded up
    to the shard count (``jax.device_put`` rejects uneven shards).
    """

    def __init__(self, capacity: int, *, val_capacity: Optional[int] = None,
                 holdout_frac: float = 0.2, sharding=None,
                 burst_capacity: int = 8):
        self._sharding = sharding
        self.burst_capacity = max(int(burst_capacity), 1)
        if sharding is not None:
            from repro.core.roles import num_shards, replicated, round_up
            nsh = num_shards(sharding)
            capacity = round_up(capacity, nsh)
            val_capacity = round_up(
                max(int(capacity) // 4, 1) if val_capacity is None
                else val_capacity, nsh)
            self._traj_sharding = replicated(sharding.mesh)
            self._write = jax.jit(_ring_write_impl, donate_argnums=(0,),
                                  out_shardings=sharding)
            self._write_burst = jax.jit(_ring_write_burst_impl,
                                        donate_argnums=(0,),
                                        out_shardings=sharding)
        else:
            self._traj_sharding = None
            self._write = _ring_write
            self._write_burst = _ring_write_burst
        self.capacity = int(capacity)
        self.val_capacity = int(val_capacity if val_capacity is not None
                                else max(capacity // 4, 1))
        self.holdout_frac = holdout_frac
        self._every = (max(int(round(1 / holdout_frac)), 2)
                       if holdout_frac > 0 else 0)
        self._train: Optional[Dict[str, jax.Array]] = None
        self._val: Optional[Dict[str, jax.Array]] = None
        self._cursor = 0          # next train write position (transitions)
        self._written = 0         # total train transitions ever written
        self._val_cursor = 0
        self._val_written = 0
        self._trajs = 0           # total trajectories ever seen

    def _alloc(self, traj) -> None:
        def zeros(t, cap):
            t = jnp.asarray(t)
            z = jnp.zeros((cap,) + t.shape[1:], t.dtype)
            if self._sharding is not None:
                z = jax.device_put(z, self._sharding)
            return z
        self._train = {k: zeros(v, self.capacity) for k, v in traj.items()}
        if self._every:     # holdout_frac == 0 never writes the val ring
            self._val = {k: zeros(v, self.val_capacity)
                         for k, v in traj.items()}

    @staticmethod
    def _fit(traj, h: int, cap: int):
        """FIFO semantics for a trajectory longer than its ring: keep the
        last ``cap`` transitions (a duplicate-index scatter would
        otherwise write in undefined order)."""
        if h <= cap:
            return traj, h
        return {k: v[-cap:] for k, v in traj.items()}, cap

    def _write_one(self, traj, val: bool) -> None:
        """Single-trajectory compiled scatter into one ring (the M=1
        path; also the fallback for mixed horizons / traj > capacity)."""
        h = int(jax.tree.leaves(traj)[0].shape[0])
        if self._traj_sharding is not None:
            # cross-role ingestion: replicate the trajectory onto the
            # owning sub-mesh (explicit device->device, no host hop)
            traj = jax.device_put(traj, self._traj_sharding)
        if val:
            traj, h = self._fit(traj, h, self.val_capacity)
            self._val = self._write(self._val, traj,
                                    self._val_cursor % self.val_capacity)
            self._val_cursor = (self._val_cursor + h) % self.val_capacity
            self._val_written += h
        else:
            traj, h = self._fit(traj, h, self.capacity)
            self._train = self._write(self._train, traj,
                                      self._cursor % self.capacity)
            self._cursor = (self._cursor + h) % self.capacity
            self._written += h

    def _write_chunk(self, chunk, h: int, val: bool) -> None:
        """One compiled burst scatter for ``len(chunk)`` equal-horizon
        trajectories: stack to (M, H, ...), zero-pad to the fixed
        ``burst_capacity`` (padding rows are dropped by index), write."""
        b, m = self.burst_capacity, len(chunk)
        stacked = {k: jnp.stack([t[k] for t in chunk]) for k in chunk[0]}
        if m < b:
            stacked = {k: jnp.concatenate(
                [v, jnp.zeros((b - m,) + v.shape[1:], v.dtype)])
                for k, v in stacked.items()}
        if self._traj_sharding is not None:
            stacked = jax.device_put(stacked, self._traj_sharding)
        rows = m * h
        if val:
            self._val = self._write_burst(
                self._val, stacked, rows,
                self._val_cursor % self.val_capacity)
            self._val_cursor = (self._val_cursor + rows) % self.val_capacity
            self._val_written += rows
        else:
            self._train = self._write_burst(
                self._train, stacked, rows, self._cursor % self.capacity)
            self._cursor = (self._cursor + rows) % self.capacity
            self._written += rows

    def _burst_to_ring(self, group, val: bool) -> None:
        """Write a group of trajectories destined for ONE ring in as few
        compiled scatters as possible. Chunks are capped at
        ``burst_capacity`` trajectories AND at ``capacity`` valid rows:
        within a chunk every target index is distinct (scatter order
        irrelevant), and a later chunk overwrites an earlier one exactly
        like sequential FIFO writes — bit-identical ring contents."""
        cap = self.val_capacity if val else self.capacity
        i = 0
        while i < len(group):
            h0 = int(jax.tree.leaves(group[i])[0].shape[0])
            chunk, rows = [group[i]], h0
            i += 1
            while i < len(group) and len(chunk) < self.burst_capacity:
                h = int(jax.tree.leaves(group[i])[0].shape[0])
                if h != h0 or rows + h > cap:
                    break
                chunk.append(group[i])
                rows += h
                i += 1
            if len(chunk) == 1:
                self._write_one(chunk[0], val)
            else:
                self._write_chunk(chunk, h0, val)

    def add_traj(self, traj) -> None:
        """Insert one trajectory (dict of (H, ...) arrays). Every
        ``1/holdout_frac``-th trajectory goes to the validation ring."""
        if self._train is None:
            self._alloc(traj)
        self._trajs += 1
        traj = {k: jnp.asarray(v) for k, v in traj.items()}
        self._write_one(
            traj, val=bool(self._every and self._trajs % self._every == 0))

    def add_trajs(self, trajs) -> None:
        """Insert a BURST of trajectories (a fleet drain) with one
        compiled scatter per ring chunk instead of one write per
        trajectory. The deterministic train/val interleave advances
        per-trajectory in arrival order, exactly as repeated
        ``add_traj`` calls would."""
        trajs = list(trajs)
        if not trajs:
            return
        if self._train is None:
            self._alloc(trajs[0])
        groups = {False: [], True: []}
        for traj in trajs:
            self._trajs += 1
            traj = {k: jnp.asarray(v) for k, v in traj.items()}
            dest = bool(self._every and self._trajs % self._every == 0)
            groups[dest].append(traj)
        self._burst_to_ring(groups[False], val=False)
        self._burst_to_ring(groups[True], val=True)

    def extend(self, trajs) -> int:
        trajs = list(trajs)
        if len(trajs) == 1:
            self.add_traj(trajs[0])
        elif trajs:
            self.add_trajs(trajs)
        return len(trajs)

    def train_view(self) -> Tuple[Optional[Dict[str, jax.Array]], int]:
        """(full-capacity storage, number of valid rows). Static shapes,
        so a jitted trainer fed from here compiles exactly once.

        The view is a BORROW, not a snapshot: the next ``add_traj``
        donates these buffers back into the ring write (in-place on
        backends with buffer aliasing). Re-fetch after every insert and
        do not hold a view across writes."""
        return self._train, self.size

    def val_view(self) -> Tuple[Optional[Dict[str, jax.Array]], int]:
        return self._val, self.val_size

    @property
    def size(self) -> int:
        return min(self._written, self.capacity)

    @property
    def val_size(self) -> int:
        return min(self._val_written, self.val_capacity)

    @property
    def total_seen(self) -> int:
        """Total trajectories ever inserted (incl. evicted ones)."""
        return self._trajs


class LocalBuffer:
    """Legacy fixed-size FIFO list buffer with a held-out validation split.

    Superseded on the hot path by :class:`ReplayBuffer` (static shapes, no
    per-epoch concatenate); kept for tooling that wants host-side
    trajectory lists."""

    def __init__(self, max_trajs: int = 200, holdout_frac: float = 0.2):
        self.max_trajs = max_trajs
        self.holdout_frac = holdout_frac
        self._train: List[Any] = []
        self._val: List[Any] = []
        self._count = 0

    def extend(self, trajs) -> int:
        for t in trajs:
            self._count += 1
            # deterministic interleave keeps val non-empty and ~frac
            if self.holdout_frac > 0 and \
                    self._count % max(int(round(1 / self.holdout_frac)), 2) == 0:
                self._val.append(t)
                if len(self._val) > max(self.max_trajs // 4, 1):
                    self._val.pop(0)
            else:
                self._train.append(t)
                if len(self._train) > self.max_trajs:
                    self._train.pop(0)
        return len(trajs)

    def _stack(self, items):
        if not items:
            return None
        cat = {k: np.concatenate([np.asarray(t[k]) for t in items], axis=0)
               for k in items[0]}
        return cat

    def train_arrays(self):
        return self._stack(self._train)

    def val_arrays(self):
        return self._stack(self._val if self._val else self._train[-1:])

    @property
    def n_train(self):
        return len(self._train)

    @property
    def total_seen(self):
        return self._count
