"""Model definition: the static structure a simulation is built from.

The reference builds models imperatively at trial start (create/initialize
processes, queues, resources — e.g. `benchmark/MM1_multi.c:91-124`).  Under
jit the structure must be static: a :class:`Model` collects process types,
blocks, queues and resources at Python time; :meth:`Model.build` freezes it
into a :class:`ModelSpec` the dispatcher closes over.  Only *state* (clock,
event slots, queue contents, locals, RNG counters) lives in the traced
pytree — one replication's state is created by ``core.loop.init_sim`` and
batched with vmap.

Block registration::

    m = Model("mm1", n_ilocals=1)
    q = m.objectqueue("buffer", capacity=1024)

    @m.block
    def a_hold(sim, p, sig):
        sim, t = api.draw(sim, random.exponential, 1.11)
        return sim, cmd.hold(t, next_pc=a_put.pc)

    @m.block
    def a_put(sim, p, sig):
        return sim, cmd.put(q.id, api.clock(sim), next_pc=a_hold.pc)

    m.process("arrival", entry=a_hold)

Forward references work because ``next_pc`` is read at trace time, after
the module is fully defined.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class QueueRef:
    id: int
    name: str
    capacity: int
    front_guard: int  # getters wait here
    rear_guard: int   # putters wait here
    record: bool = True  # queue-length StepAccum recording


@dataclasses.dataclass
class ResourceRef:
    id: int
    name: str
    guard: int
    record: bool = True


@dataclasses.dataclass
class PoolRef:
    id: int
    name: str
    capacity: float
    guard: int
    record: bool = True


@dataclasses.dataclass
class BufferRef:
    id: int
    name: str
    capacity: float
    initial: float
    front_guard: int  # getters wait here
    rear_guard: int   # putters wait here
    record: bool = True


@dataclasses.dataclass
class PQueueRef:
    id: int
    name: str
    capacity: int
    front_guard: int
    rear_guard: int
    record: bool = True


@dataclasses.dataclass
class ConditionRef:
    id: int
    name: str
    guard: int
    predicate: Callable  # predicate(sim, pid) -> bool array
    #: guard ids this condition OBSERVES (parity: cmb_resourceguard_register,
    #: `src/cmb_resourceguard.c:313-330`): any signal on an observed guard —
    #: a release, put, rollback, drop-on-exit — forwards into cond_signal,
    #: so waiters re-evaluate without the model signalling at every site
    observes: tuple = ()


@dataclasses.dataclass
class ProcessType:
    name: str
    entry_pc: int
    prio: int
    count: int
    #: False = the rows exist but stay CREATED until api.spawn activates
    #: them (parity: runtime cmb_process_create/start — under jit the
    #: process POOL is declared, activation is dynamic)
    start: bool = True
    first_pid: int = -1  # assigned at build


@dataclasses.dataclass
class ModelSpec:
    """Frozen model structure (everything static the stepper needs)."""

    name: str
    blocks: List[Callable]
    proc_entry: np.ndarray     # [P] i32
    proc_prio: np.ndarray      # [P] i32
    #: [P] bool — False rows are spawn-pool members: they stay CREATED
    #: at init until api.spawn activates them
    proc_start: np.ndarray
    proc_names: List[str]
    queues: List[QueueRef]
    resources: List[ResourceRef]
    pools: List[PoolRef]
    buffers: List[BufferRef]
    pqueues: List[PQueueRef]
    conditions: List[ConditionRef]
    n_guards: int
    guard_cap: int
    event_cap: int
    queue_cap_max: int
    pqueue_cap_max: int
    n_flocals: int
    n_ilocals: int
    #: bound on non-yielding command chains for the Pallas-kernel stepper's
    #: masked fori (the XLA path uses a dynamic while with the large
    #: MAX_CHAIN runaway bound); raise it for models that chain many
    #: non-blocking commands between yields
    max_chain: int
    user_init: Optional[Callable[..., Any]]
    user_handlers: List[Callable]
    #: pcs of blocks dispatched OUTSIDE the Pallas kernel at chunk
    #: boundaries (see Model.boundary_block); empty for most models
    boundary_pcs: tuple = ()
    #: steps a lane of the XLA boundary chunk may run in one round before
    #: the boundary phase (see Model); None: until every lane waits
    boundary_round_steps: Optional[int] = None

    @property
    def n_procs(self) -> int:
        return len(self.proc_entry)


class Model:
    """Mutable model builder (Python-time only)."""

    def __init__(
        self,
        name: str,
        *,
        n_flocals: int = 0,
        n_ilocals: int = 0,
        event_cap: int = 16,
        guard_cap: int = 8,  # accepted for compat; dense guards cannot
        # overflow (capacity is n_procs by construction), so this no
        # longer sizes anything
        max_chain: int = 16,
        boundary_round_steps: Optional[int] = None,
    ):
        """``boundary_round_steps`` (models with boundary blocks): the XLA
        chunk runs the boundary phase once every live lane waits for it
        or has run this many steps in the round, whichever comes first —
        a little above the events a lane dispatches between two of its
        boundary dispatches, so a lane that needs more than usual does
        not hold the whole wave.  ``None`` waits for every lane."""
        self.name = name
        self.boundary_round_steps = boundary_round_steps
        self.n_flocals = n_flocals
        self.n_ilocals = n_ilocals
        self.event_cap = event_cap
        self.guard_cap = guard_cap
        self.max_chain = max_chain
        self._blocks: List[Callable] = []
        self._types: List[ProcessType] = []
        self._queues: List[QueueRef] = []
        self._resources: List[ResourceRef] = []
        self._pools: List[PoolRef] = []
        self._buffers: List[BufferRef] = []
        self._pqueues: List[PQueueRef] = []
        self._conditions: List[ConditionRef] = []
        self._n_guards = 0
        self._user_init: Optional[Callable] = None
        self._user_handlers: List[Callable] = []
        self._boundary_pcs: List[int] = []

    # --- structure -----------------------------------------------------

    def block(self, fn: Callable) -> Callable:
        """Register a block; sets ``fn.pc`` to its global index."""
        fn.pc = len(self._blocks)
        self._blocks.append(fn)
        return fn

    def boundary_block(self, fn: Callable) -> Callable:
        """Register a block whose dispatch runs OUTSIDE the Pallas kernel,
        at a chunk boundary, as plain XLA (the physics-hook analog of the
        reference launching CUDA from a coroutine, `tutorial/tut_5_3.c`).

        Use for bulk work over whole component arrays — batched matmuls,
        big reductions — that would otherwise execute masked on EVERY
        kernel event: the kernel freezes a lane whose next dispatch
        targets this block, and the chunk driver applies one ordinary
        XLA engine step (MXU and all) to the frozen lanes between
        chunks.  Semantics are identical to a normal block — same event
        order, same statistics — and the XLA path ignores the marker.

        Constraint: a boundary block must be entered by RESUMES (process
        entry, hold/wake continuations), not mid-chain via cmd.jump or a
        completed command's next_pc — the kernel flags such an entry as
        a failed replication (ERR_BOUNDARY)."""
        fn = self.block(fn)
        self._boundary_pcs.append(fn.pc)
        return fn

    def process(self, name: str, entry, *, prio: int = 0, count: int = 1,
                start: bool = True):
        """Declare ``count`` instances of a process type starting at block
        ``entry`` (a function registered with :meth:`block`).

        ``start=False`` declares a SPAWN POOL: the rows exist but stay
        CREATED until a block activates one with ``api.spawn(sim, pt)``
        — the jit answer to the reference's runtime process creation
        (`cmb_process_create`/`cmb_process_start`); finished rows are
        recycled by later spawns."""
        pt = ProcessType(name, entry.pc, prio, count, start)
        self._types.append(pt)
        return pt

    def _guard(self) -> int:
        g = self._n_guards
        self._n_guards += 1
        return g

    def objectqueue(
        self, name: str, capacity: int, record: bool = True
    ) -> QueueRef:
        """FIFO of f64 payloads (parity: cmb_objectqueue; the reference's
        void* objects become a float payload — typically a timestamp or an
        index into user state).  ``record=False`` disables queue-length
        recording at trace time (parity: the reference's optional
        recording; measurable speedup in hot models)."""
        q = QueueRef(
            id=len(self._queues),
            name=name,
            capacity=capacity,
            front_guard=self._guard(),
            rear_guard=self._guard(),
            record=record,
        )
        self._queues.append(q)
        return q

    def resource(self, name: str, record: bool = True) -> ResourceRef:
        """Single-holder resource (parity: cmb_resource)."""
        r = ResourceRef(
            id=len(self._resources), name=name, guard=self._guard(),
            record=record,
        )
        self._resources.append(r)
        return r

    def resourcepool(
        self, name: str, capacity: float, record: bool = True
    ) -> PoolRef:
        """Counting resource of ``capacity`` fungible units (parity:
        cmb_resourcepool)."""
        p = PoolRef(
            id=len(self._pools), name=name, capacity=float(capacity),
            guard=self._guard(), record=record,
        )
        self._pools.append(p)
        return p

    def buffer(
        self, name: str, capacity: float, initial: float = 0.0,
        record: bool = True,
    ) -> BufferRef:
        """Producer-consumer store of a fungible amount (parity: cmb_buffer)."""
        b = BufferRef(
            id=len(self._buffers), name=name, capacity=float(capacity),
            initial=float(initial), front_guard=self._guard(),
            rear_guard=self._guard(), record=record,
        )
        self._buffers.append(b)
        return b

    def priorityqueue(
        self, name: str, capacity: int, record: bool = True
    ) -> PQueueRef:
        """Object queue ordered by per-item priority, FIFO within equal
        priorities (parity: cmb_priorityqueue)."""
        q = PQueueRef(
            id=len(self._pqueues), name=name, capacity=capacity,
            front_guard=self._guard(), rear_guard=self._guard(),
            record=record,
        )
        self._pqueues.append(q)
        return q

    def condition(
        self, name: str, predicate: Callable, observes=()
    ) -> ConditionRef:
        """Condition variable: processes wait until ``predicate(sim, pid)``
        holds at a signal (parity: cmb_condition; the reference's C
        predicate pointer becomes a traced function registered here).

        ``observes`` — components (resources, pools, buffers, queues,
        priority queues) whose state changes can satisfy the predicate:
        any guard signal they emit (release, put, rollback, drop-on-exit)
        auto-forwards into a signal of this condition, so the model never
        has to call ``api.cond_signal`` at release sites (parity:
        ``cmb_resourceguard_register``, `src/cmb_resourceguard.c:313-330`,
        the mechanism the reference's harbor tutorial rests on,
        `tutorial/tut_4_1.c:499-501`).  Signals driven by non-component
        state (e.g. a tide process updating user state) still need the
        explicit ``api.cond_signal``.
        """
        gids = []
        for comp in observes:
            found = False
            for attr in ("guard", "front_guard", "rear_guard"):
                g = getattr(comp, attr, None)
                if g is not None:
                    gids.append(g)
                    found = True
            if not found:
                raise TypeError(
                    f"condition {name!r}: observes entry {comp!r} has no "
                    "guard — pass component refs (resource/pool/buffer/"
                    "queue/pqueue)"
                )
        c = ConditionRef(
            id=len(self._conditions), name=name, guard=self._guard(),
            predicate=predicate, observes=tuple(gids),
        )
        self._conditions.append(c)
        return c

    def user_state(self, fn: Callable) -> Callable:
        """Register ``fn(params) -> pytree`` building per-replication user
        state (the reference's trial struct, `include/cimba.h:100-118`)."""
        self._user_init = fn
        return fn

    def handler(self, fn: Callable) -> Callable:
        """Register a user event handler ``fn(sim, subj, arg) -> sim``;
        sets ``fn.kind`` for use with api.schedule (parity: arbitrary
        (action, subject, object) events, `include/cmb_event.h:75-180`)."""
        # kinds 0/1 are the framework's K_PROC/K_TIMER (core.loop)
        fn.kind = 2 + len(self._user_handlers)
        self._user_handlers.append(fn)
        return fn

    # --- freeze ----------------------------------------------------------

    def build(self) -> ModelSpec:
        if not self._types:
            raise ValueError("model has no processes")
        entries, prios, names, started = [], [], [], []
        for pt in self._types:
            pt.first_pid = len(entries)
            for k in range(pt.count):
                entries.append(pt.entry_pc)
                prios.append(pt.prio)
                started.append(pt.start)
                names.append(pt.name if pt.count == 1 else f"{pt.name}[{k}]")
        from cimba_tpu.utils import logger as _logger

        _logger.names_set(names)  # log lines render name(pid)
        return ModelSpec(
            name=self.name,
            blocks=list(self._blocks),
            proc_entry=np.asarray(entries, np.int32),
            proc_prio=np.asarray(prios, np.int32),
            proc_start=np.asarray(started, np.bool_),
            proc_names=names,
            queues=list(self._queues),
            resources=list(self._resources),
            pools=list(self._pools),
            buffers=list(self._buffers),
            pqueues=list(self._pqueues),
            conditions=list(self._conditions),
            n_guards=max(self._n_guards, 1),
            guard_cap=self.guard_cap,
            event_cap=self.event_cap,
            queue_cap_max=max([q.capacity for q in self._queues], default=1),
            pqueue_cap_max=max([q.capacity for q in self._pqueues], default=1),
            n_flocals=self.n_flocals,
            n_ilocals=self.n_ilocals,
            max_chain=self.max_chain,
            user_init=self._user_init,
            user_handlers=list(self._user_handlers),
            boundary_pcs=tuple(self._boundary_pcs),
            boundary_round_steps=self.boundary_round_steps,
        )