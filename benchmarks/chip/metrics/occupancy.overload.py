"""Pool layer: budgeted decode tokens emitted over (decode steps x slots)
in the window, from the pool's own counters, in percent."""


def read(run):
    a, b = run.win.pool_start, run.win.pool_end
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    return 100.0 * (b["decode_emitted"] - a["decode_emitted"]) / (
        steps * b["slots"])
