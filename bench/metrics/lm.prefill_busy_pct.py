"""Share of the device's busy time spent in the prefill programs (0 where
the traced slice admitted no request)."""

# ``ServingEngine`` jits ``model.prefill``, a lambda: its programs are
# ``jit__lambda(<fingerprint>)``, one per prompt length.
PROGRAM = r"^jit__lambda\("


def read(ctx):
    if ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.module_total(PROGRAM) / ctx.trace.busy_s
