"""decode_host_ms: the mean host time of a decode step outside its
dispatch-to-tokens stretch (page growth and page-table uploads, per-slot
bookkeeping, finishing requests), while the device has nothing queued,
over the window's steps: ``engine.step`` less its ``engine.decode``."""
from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None or not w.steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 - program_spans.dur(dec)
                     for s, dec in w.steps) / len(w.steps)
