"""expert_useful_share: the routed picks that landed on the experts this
chip holds, over the (token, held expert) rows the dense dropless sweep
computed, summed over the window's decode steps: the ``picks_here`` and
``expert_rows`` of the program's ``engine.decode`` spans.  A program
whose spans carry no such counts reads None."""
from bench import program_spans


def read(run):
    w = program_spans.window(run)
    if w is None:
        return None
    dec = [d for _, ds in w.steps for d in ds if "expert_rows" in d.attrs]
    rows = sum(d.attrs["expert_rows"] for d in dec)
    if not rows:
        return None
    return 100.0 * sum(d.attrs["picks_here"] for d in dec) / rows
