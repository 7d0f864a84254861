"""Engine layer (faas/engine.py realtime loop, LocalDuetBackend): per
invocation, the engine's wall minus the walls of the `execute` calls."""


def read(ctx):
    view = ctx.view
    executes = view.spans("bench.execute")
    if not executes:
        return None
    engine_ns = sum(e - s for _, s, e in view.spans("bench.engine"))
    execute_ns = sum(e - s for _, s, e in executes)
    return (engine_ns - execute_ns) / len(executes) * 1e-6
