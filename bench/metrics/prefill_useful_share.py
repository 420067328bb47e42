"""prefill_useful_share: the prompt tokens the window's admission rounds
prefilled (their uncached tails) over the token rows their prefills
computed (slots x padded width): the ``tokens``, ``slots`` and ``width``
of the program's ``engine.prefill`` spans."""
from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None:
        return None
    pre = [p for _, ps in w.rounds for p in ps]
    rows = sum(p.attrs["slots"] * p.attrs["width"] for p in pre)
    if not rows:
        return None
    return 100.0 * sum(p.attrs["tokens"] for p in pre) / rows
