"""The drill that holds the decode step's view ladder to the whole
view, shared by tests/test_serving_engine.py (float pool) and
tests/test_quant.py (int8 pool).

An engine with the ladder and its twin held to the one whole-view
rung are ticked by hand (never started, so every step boundary is
deterministic) over the same requests; each step dispatch of the
first is recorded with the view it was given and what its active rows
needed, reckoned here from the scheduler's slots and not by the code
under test."""
from veles_tpu.serving import ContinuousEngine
from veles_tpu.serving.engine import make_request
from veles_tpu.serving.scheduler import Ticket

#: 16 pages of 4 positions a slot: rungs of 64 and 32 positions
GEOMETRY = dict(max_slots=3, buckets=(8, 24), max_context=64, page_size=4)


def requests(prompt):
    """One long greedy answer that crosses into the whole view while
    its sampled and greedy co-tenants stay short (``prompt(seed,
    length)`` draws a prompt). The long one's prompt is 4 tokens, so
    that at ``decode_block`` 4 every chunk of it ends on a multiple of
    4, and one exactly at the half rung's last position."""
    return [make_request(prompt(400, 4), 56, seed=1),
            make_request(prompt(401, 4), 20, temperature=0.8, seed=2),
            make_request(prompt(402, 3), 3),
            make_request(prompt(403, 12), 6, temperature=0.8, seed=3),
            make_request(prompt(404, 20), 30, temperature=0.7, seed=4)]


def twins(wf, name, **knobs):
    """(the engine with its ladder, the same engine held to the whole
    view); ``knobs`` over ``GEOMETRY``."""
    knobs = dict(GEOMETRY, **knobs)
    ladder = ContinuousEngine(wf, name=name + "_ladder", **knobs)
    whole = ContinuousEngine(wf, name=name + "_whole", **knobs)
    whole.view_ladder = whole.view_ladder[:1]
    return ladder, whole


def record_rungs(engine):
    """Every later step dispatch of ``engine`` appends ``(pages of the
    table it was given, positions its active rows needed)``."""
    seen, program = [], engine._program

    def recording(kind, bucket=None):
        prog = program(kind, bucket)
        if kind != "step":
            return prog

        def step(params, tok, pos, temp, mask, tables, *rest):
            need = max(min(s.t_p + s.n_new,
                           int(pos[s.idx]) + engine.decode_block)
                       for s in engine.scheduler.active() if mask[s.idx])
            seen.append((tables.shape[1], need))
            assert tables.shape[1] == bucket
            return prog(params, tok, pos, temp, mask, tables, *rest)
        return step
    engine._program = recording
    return seen


def tick_until(engine, done, limit=3000):
    for _ in range(limit):
        if done():
            return
        engine._tick()
    assert done(), "the engine did not get there in %d ticks" % limit


def serve_by_ticks(engine, reqs):
    tickets = [Ticket() for _ in reqs]
    for req, ticket in zip(reqs, tickets):
        assert engine.submit(dict(req), ticket)
    tick_until(engine, lambda: all(t.event.is_set() for t in tickets))
    for ticket in tickets:
        assert ticket.error is None, ticket.error
    return [t.result["tokens"] for t in tickets]


def assert_shortest_rungs(engine, seen):
    """No dispatch's view was shorter than an active row needed, each
    was the shortest rung that is not, and the ladder engaged."""
    size = engine.page_size
    assert seen
    for pages, need in seen:
        assert pages * size >= need, (pages, need)
        assert pages == min(r for r in engine.view_ladder
                            if r * size >= need), (pages, need)
    assert len({pages for pages, _ in seen}) > 1
    assert engine.programs_built <= engine.programs_bound()
    share = engine.stats()["view_share"]
    assert abs(share - sum(p for p, _ in seen)
               / (len(seen) * engine.pages_per_slot)) < 1e-4
    assert share < 1.0
