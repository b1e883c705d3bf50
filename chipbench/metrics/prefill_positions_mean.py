"""Prompt positions that a tick prefills beside its decode step, over the
traced slice: the rise of ``veles_serving_prefill_positions_total`` (the
bucket of every prefill the target model ran, a chunk's length on the
chunked path; serving/engine.py ``_admit``) over the slice's decode
dispatches.

A check on the traffic, not a number to lower: the harness draws the
prompts, a faster program admits more of them a second, and a window's
first wave of prompts echoes through the slice when its requests end
together. ``tick_ms.prefill`` and the rate move with it, and this says by
how much the draw differed. ``BENCHMARK.json`` must give every metric a
direction and has no neutral one; PERF.md section 3 says the same. A
program without the counter (or a slice without decode dispatches) gives
nothing."""


def read(ctx):
    counters = (ctx["report"].get("slice") or {}).get("counters") or {}
    steps = counters.get("veles_serving_decode_dispatches_total", 0)
    positions = counters.get("veles_serving_prefill_positions_total")
    if not steps or positions is None:
        return None
    return positions / steps
