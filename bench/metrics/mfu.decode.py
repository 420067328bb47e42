"""mfu.decode: the model FLOPs the window's decode steps required (routed
experts only, the real vocabulary, attention over the visible keys;
``bench/flops.py``), over the decode steps' host time times the chip's
bfloat16 peak."""


def read(run):
    c = run.counters
    if not c.get("step_s"):
        return None
    return 100.0 * c["decode_flops"] / (c["step_s"] * run.peak["bf16_flops"])
