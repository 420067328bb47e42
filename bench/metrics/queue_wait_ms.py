"""queue_wait_ms: the median wait of the window's requests in the
engine's queue, from submit (or the requeue after an eviction) to the
start of the admission round that takes them: the program's
``engine.queue`` spans."""
import statistics

from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None or not w.queue:
        return None
    return 1e3 * statistics.median(s.t1 - s.t0 for s in w.queue)
