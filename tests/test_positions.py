"""Queue/priority-queue position queries and logger context (parity:
cmb_objectqueue_position `include/cmb_objectqueue.h:199`,
cmb_priorityqueue_position `include/cmb_priorityqueue.h:140`,
logger time formatter + reproduction seed `src/cmb_logger.c:94-227`).
"""

import jax
import jax.numpy as jnp
import numpy as np

from cimba_tpu.core import api, cmd
from cimba_tpu.core import loop as cl
from cimba_tpu.core.model import Model
from cimba_tpu.utils import logger


def _queued_sim(items):
    """A sim whose single object queue holds ``items`` (producer only)."""
    m = Model("posq", n_ilocals=1, event_cap=16)
    q = m.objectqueue("q", capacity=8, record=False)

    @m.block
    def produce(sim, p, sig):
        k = api.local_i(sim, p, 0)
        done = k >= len(items)
        sim = api.add_local_i(sim, p, 0, 1)
        vals = jnp.asarray(items, jnp.float64)
        item = vals[jnp.clip(k, 0, len(items) - 1)]
        return sim, cmd.select(
            done, cmd.exit_(), cmd.put(q.id, item, next_pc=produce.pc)
        )

    m.process("producer", entry=produce)
    spec = m.build()
    out = jax.jit(cl.make_run(spec))(cl.init_sim(spec, 0, 0, None))
    assert int(out.err) == 0
    return out, q


def test_objectqueue_position_first_match_from_front():
    out, q = _queued_sim([5.0, 7.0, 5.0, 9.0])
    assert int(api.queue_position(out, q, 5.0)) == 1  # first match wins
    assert int(api.queue_position(out, q, 7.0)) == 2
    assert int(api.queue_position(out, q, 9.0)) == 4
    assert int(api.queue_position(out, q, 42.0)) == 0  # absent


def test_objectqueue_position_respects_ring_wrap():
    """Head != 0: positions count from the logical front, not slot 0."""
    m = Model("wrapq", n_ilocals=1, event_cap=16)
    q = m.objectqueue("q", capacity=4, record=False)

    # fill 4, drain 2, add 2: ring head has wrapped
    @m.block
    def drive(sim, p, sig):
        k = api.local_i(sim, p, 0)
        sim = api.add_local_i(sim, p, 0, 1)
        # puts of 1,2,3,4 then gets x2 then puts of 5,6
        return sim, cmd.select(
            k < 4,
            cmd.put(q.id, (k + 1).astype(jnp.float64), next_pc=drive.pc),
            cmd.select(
                k < 6,
                cmd.get(q.id, next_pc=drive.pc),
                cmd.select(
                    k < 8,
                    cmd.put(
                        q.id, (k - 1).astype(jnp.float64), next_pc=drive.pc
                    ),
                    cmd.exit_(),
                ),
            ),
        )

    m.process("driver", entry=drive)
    spec = m.build()
    out = jax.jit(cl.make_run(spec))(cl.init_sim(spec, 0, 0, None))
    assert int(out.err) == 0
    # queue now holds (front→rear): 3, 4, 5, 6
    for item, pos in [(3.0, 1), (4.0, 2), (5.0, 3), (6.0, 4), (1.0, 0)]:
        assert int(api.queue_position(out, q, item)) == pos


def test_priorityqueue_position_dequeue_order():
    m = Model("pospq", n_ilocals=1, event_cap=16)
    pq = m.priorityqueue("pq", capacity=8, record=False)
    # (item, prio): dequeue order is prio desc then FIFO
    puts = [(10.0, 1.0), (20.0, 5.0), (30.0, 5.0), (40.0, 0.0)]

    @m.block
    def produce(sim, p, sig):
        k = api.local_i(sim, p, 0)
        done = k >= len(puts)
        sim = api.add_local_i(sim, p, 0, 1)
        items = jnp.asarray([x for x, _ in puts], jnp.float64)
        prios = jnp.asarray([y for _, y in puts], jnp.float64)
        kk = jnp.clip(k, 0, len(puts) - 1)
        return sim, cmd.select(
            done,
            cmd.exit_(),
            cmd.pq_put(pq.id, items[kk], prios[kk], next_pc=produce.pc),
        )

    m.process("producer", entry=produce)
    spec = m.build()
    out = jax.jit(cl.make_run(spec))(cl.init_sim(spec, 0, 0, None))
    assert int(out.err) == 0
    # dequeue order: 20 (prio 5, first), 30 (prio 5, second), 10, 40
    for item, pos in [(20.0, 1), (30.0, 2), (10.0, 3), (40.0, 4), (77.0, 0)]:
        assert int(api.pqueue_position(out, pq, item)) == pos


def test_logger_timeformatter_and_seed_context(capfd):
    """Custom time formatter applies; warning lines carry the replay
    (key, ctr) stream id and the replication index."""
    from cimba_tpu.models import mm1

    spec, _ = mm1.build()
    sim = cl.init_sim(spec, 123, 3, mm1.params(5))
    logger.timeformatter_set(lambda t: f"<T{t:.1f}>")
    try:
        sim2 = logger.warning(sim, 0, "odd thing n={n}", n=7)
        jax.effects_barrier()
    finally:
        logger.timeformatter_set(None)
    out = capfd.readouterr().out
    assert "<T0.0>" in out
    assert "r=3" in out
    assert "odd thing n=7" in out
    assert "replay: key=0x" in out and "ctr=" in out


def test_logger_default_format_includes_rep(capfd):
    from cimba_tpu.models import mm1

    spec, _ = mm1.build()
    sim = cl.init_sim(spec, 123, 11, mm1.params(5))
    logger.warning(sim, 1, "plain")
    jax.effects_barrier()
    out = capfd.readouterr().out
    assert "r=11" in out
    assert "replay: key=0x" in out


def test_objectqueue_position_second_queue():
    """Two queues of different capacities share one flat ring (queue
    q's slot k at q * widest + k): positions on the second, wrapped
    queue count from its own head, modulo its own capacity, and never
    see the first queue's items."""
    m = Model("twoq", n_ilocals=1, event_cap=16)
    q1 = m.objectqueue("q1", capacity=8, record=False)
    q2 = m.objectqueue("q2", capacity=4, record=False)

    # k 0-2: 10, 20, 30 into q1; k 3-6: 1..4 into q2; k 7-8: two gets
    # from q2; k 9-10: 5, 6 into q2, which wraps its ring
    @m.block
    def drive(sim, p, sig):
        k = api.local_i(sim, p, 0)
        sim = api.add_local_i(sim, p, 0, 1)
        f = k.astype(jnp.float64)
        return sim, cmd.select(
            k < 3,
            cmd.put(q1.id, 10.0 * (f + 1), next_pc=drive.pc),
            cmd.select(
                k < 7,
                cmd.put(q2.id, f - 2, next_pc=drive.pc),
                cmd.select(
                    k < 9,
                    cmd.get(q2.id, next_pc=drive.pc),
                    cmd.select(
                        k < 11,
                        cmd.put(q2.id, f - 4, next_pc=drive.pc),
                        cmd.exit_(),
                    ),
                ),
            ),
        )

    m.process("driver", entry=drive)
    spec = m.build()
    out = jax.jit(cl.make_run(spec))(cl.init_sim(spec, 0, 0, None))
    assert int(out.err) == 0
    assert out.queues.items.shape == (2 * 8,)
    # q2 now holds (front->rear): 3, 4, 5, 6, with 5 and 6 in slots 0, 1
    for item, pos in [(3.0, 1), (4.0, 2), (5.0, 3), (6.0, 4), (1.0, 0),
                      (10.0, 0)]:
        assert int(api.queue_position(out, q2, item)) == pos
    for item, pos in [(10.0, 1), (20.0, 2), (30.0, 3), (3.0, 0)]:
        assert int(api.queue_position(out, q1, item)) == pos
