"""admit_host_ms: the mean host time of an admission round outside its
prefill (prefix match and page hashes, page allocation, page-table
uploads, publishing pages, first-token bookkeeping), over the window's
rounds that admitted: ``engine.admit`` less its ``engine.prefill``."""
from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None or not w.rounds:
        return None
    return 1e3 * sum(a.t1 - a.t0 - program_spans.dur(pre)
                     for a, pre in w.rounds) / len(w.rounds)
