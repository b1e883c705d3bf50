"""The drill that holds the decode tick's new order to the old one,
shared by tests/test_serving_engine.py (float pool), tests/test_quant.py
(int8 pool), tests/test_hybrid_serving.py (the hybrid stack) and
tests/test_export_aot.py (an exported step).

An engine that dispatches step n+1 before it reads step n's tokens and
its twin held to the serial order (the step in flight is read right
after its own dispatch, every tick: what ``_drain`` restores wherever
the engine calls it) are ticked by hand over the same requests. Each
step dispatch and each read of a step's tokens is recorded in the order
it happened, from outside the code under test."""
import numpy

from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import Ticket

# serve_by_ticks and tick_until: the callers' too
from ladder_drill import GEOMETRY, serve_by_ticks, tick_until  # noqa: F401


def hold_serial(engine):
    """``engine``, drained after every plain step: the order in which
    the tick ran before a step was ever left in flight."""
    decode = engine._decode

    def serial(params):
        decode(params)
        engine._drain()
    engine._decode = serial
    return engine


def twins(wf, name, **knobs):
    """(the engine as it ships, the same engine held to the serial
    order); ``knobs`` over ``ladder_drill.GEOMETRY``."""
    knobs = dict(GEOMETRY, **knobs)
    return (ContinuousEngine(wf, name=name + "_ahead", **knobs),
            hold_serial(ContinuousEngine(wf, name=name + "_serial",
                                         **knobs)))


class _Logged:
    """A step's tokens on their way to the host: says when they are
    read."""

    def __init__(self, value, n, events):
        self.value, self.n, self.events = value, n, events

    def __array__(self, dtype=None, copy=None):
        self.events.append(("read", self.n))
        return numpy.asarray(self.value)


def record_order(engine, kind="step"):
    """Every later dispatch of ``engine``'s ``kind`` program appends
    ``("dispatch", n, rows masked in)`` and every read of its tokens
    ``("read", n)``, n counting the dispatches from 1."""
    events, program = [], engine._program

    def recording(which, bucket=None):
        prog = program(which, bucket)
        if which != kind:
            return prog

        def step(*args):
            n = 1 + sum(1 for e in events if e[0] == "dispatch")
            events.append(("dispatch", n, int(numpy.asarray(args[4]).sum())))
            # the step's tokens go back into it as they were returned
            out = prog(*(a.value if isinstance(a, _Logged) else a
                         for a in args))
            return (_Logged(out[0], n, events),) + tuple(out[1:])
        return step
    engine._program = recording
    return events


def ahead_of(events):
    """How many dispatches were issued with the step before still
    unread. Also holds the order to its rules: steps are read in the
    order they were dispatched, each once, and never is more than one
    unread when the next is dispatched."""
    unread, ahead, read = [], 0, set()
    for event in events:
        if event[0] == "dispatch":
            assert len(unread) <= 1, events
            ahead += len(unread)
            unread.append(event[1])
        else:
            assert unread and unread.pop(0) == event[1], events
            assert event[1] not in read
            read.add(event[1])
    return ahead


def row_steps(events, block):
    """Row-steps the recorded dispatches ran."""
    return block * sum(e[2] for e in events if e[0] == "dispatch")


def ender(answer, draw, n_new, temperature=0.0, seed=0, earliest=3):
    """A request that ends on an ``eos_id`` the host cannot foresee, and
    the tokens it must give. ``answer(req)`` gives a request's tokens
    (``solo`` with the workflow, or a serial twin's serving where the
    plane has no other reference). Taken is the first prompt of
    ``draw(0)``, ``draw(1)``, ... whose tokens without an ``eos_id``
    hold a token that comes first no earlier than ``earliest`` and not
    at the end (a small model's greedy answers often repeat one
    token); the latest such token is the ``eos_id``."""
    for i in range(64):
        req = make_request(draw(i), n_new, temperature=temperature,
                           seed=seed)
        reference = answer(req)
        first = {}
        for at, tok in enumerate(reference):
            first.setdefault(tok, at)
        late = [at for at in first.values()
                if earliest <= at < len(reference) - 2]
        if late:
            at = max(late)
            return (dict(req, eos_id=int(reference[at])),
                    list(reference[:at + 1]))
    raise AssertionError("no prompt drawn ends on a late token")


def submit_all(engine, reqs, **ticket):
    tickets = [Ticket(**ticket) for _ in reqs]
    for req, t in zip(reqs, tickets):
        assert engine.submit(dict(req), t)
    return tickets


def solo(wf, req):
    """The request's tokens by the scan decoder, cut at its
    ``eos_id``."""
    from veles_tpu.nn import sampling
    toks = sampling.generate(wf, req["prompt"], req["n_new"],
                             temperature=req["temperature"],
                             seed=req["seed"])
    eos = req.get("eos_id")
    if eos is not None and eos in toks:
        toks = toks[:toks.index(eos) + 1]
    return toks

