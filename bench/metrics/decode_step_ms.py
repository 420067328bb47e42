"""decode_step_ms: host time of the engine's decode steps (page growth,
the jitted step, the argmax on the host, finishing requests), over the
number of steps."""


def read(run):
    c = run.counters
    if not c.get("steps"):
        return None
    return 1e3 * c["step_s"] / c["steps"]
