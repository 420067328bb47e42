"""admit_ms: host time of the admission rounds that admitted at least
one request (prefix match, page tables, the round's ragged prefill, its
argmax on the host), over the number of such rounds."""


def read(run):
    c = run.counters
    if not c.get("admit_rounds"):
        return None
    return 1e3 * c["admit_s"] / c["admit_rounds"]
