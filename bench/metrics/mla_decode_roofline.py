"""mla_decode_roofline: the least time the Pallas MLA paged-decode kernel
could take over the traced decode steps, over its device time in the
trace.  The least time of a call is the larger of its FLOPs over the
bfloat16 peak and its bytes (the resident latent pages of the active
rows, their query and output rows) over HBM bandwidth, once per MLA
layer; ``bench/flops_deepseek_v2.py`` counts both."""
from bench import flops_deepseek_v2

KERNEL = "mla_paged_decode"


def read(run):
    red = run.reduced
    if red is None:
        return None
    t_kernel = red.kernel_s(KERNEL)
    steps = [pos for t0, t1, pos in run.steps if run.traced(t0, t1)]
    if t_kernel <= 0 or not steps:
        return None
    c = run.cell.config
    ps = run.cell.traffic["page_size"]
    least, by_bytes = 0.0, 0
    for pos in steps:
        fl, by = flops_deepseek_v2.mla_decode_cost(c, pos, ps)
        t_fl, t_by = fl / run.peak["bf16_flops"], by / run.peak["hbm_bytes_per_s"]
        least += max(t_fl, t_by)
        by_bytes += t_by >= t_fl
    least *= c["num_hidden_layers"]
    run.log(f"mla_decode_roofline: {len(steps)} traced steps, bound by "
            f"bytes in {by_bytes}, kernel {t_kernel:.6f} s, least "
            f"{least:.6f} s")
    return 100.0 * least / t_kernel
