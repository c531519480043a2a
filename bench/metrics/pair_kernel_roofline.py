"""The pair kernel's share of its roofline over the traced jobs: the least
time the chip could take for the work (benchlib/roofline.py, from the
verified answers) over the kernel's device time. Records which bound
(bytes or ops) applies in ctx["roofline_bound"]."""
from benchlib import layers, roofline


def read(ctx):
    out = ctx["outcome"]
    kernel_s = layers.device_seconds(ctx["trace"], layers.PAIR_KERNEL)
    if kernel_s is None or ctx["peak"] is None:
        return None
    counts = out.layer["partition_counts"]
    least, bound = 0.0, {}
    for q in out.layer["mix"]:
        answer = ctx["want"][id(q)]
        s, b = roofline.least_seconds(
            roofline.query_bytes(counts, ctx["cfg"]["codec"],
                                 len(q["radii_rad"])),
            roofline.query_ops(answer, out.layer["rows"]), ctx["peak"])
        least += s
        bound[b] = bound.get(b, 0.0) + s
    ctx["roofline_bound"] = max(bound, key=bound.get)
    return 100.0 * least * out.layer["jobs"] / kernel_s
