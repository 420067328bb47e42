"""prefill_ms: the mean time of an admission round's prefill, from its
first device dispatch (a copy-on-write copy or the prefill) to its next
tokens on the host, over the window's rounds that admitted: the
program's ``engine.prefill`` spans."""
from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None or not w.rounds:
        return None
    return 1e3 * sum(program_spans.dur(pre)
                     for _, pre in w.rounds) / len(w.rounds)
