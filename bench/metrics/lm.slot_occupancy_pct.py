"""Share of the scheduler's decode slots that served a request, from its
own counters over the traced window:
``tokens_emitted / (steps_run x num_slots)``."""


def read(ctx):
    steps = ctx.counters.get("steps_run", 0.0)
    if steps <= 0:
        return None
    return 100.0 * ctx.counters["tokens_emitted"] / (steps
                                                     * ctx.counts["slots"])
