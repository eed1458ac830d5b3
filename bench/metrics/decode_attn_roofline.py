"""Share of its roofline that the decode-attention kernel reaches: the
larger of operations / bf16 peak and bytes / HBM bandwidth, for the live
lengths of every token decoded in the window (bench/flops.decode_attention),
over the kernel's summed device time."""
import sys

from bench.trace import KERNEL, op_time_s


def read(ctx):
    tr, n = ctx["trace"], ctx["counts"]
    if tr is None or not tr.devices or not n.get("decode_lengths"):
        return None
    t, calls = op_time_s(tr, min(tr.devices), KERNEL["decode_attention"])
    if not calls:
        return None
    ops, nbytes = ctx["flops"].decode_attention(n["dims"], n["decode_lengths"])
    t_ops = ops / ctx["peaks"]["bf16_flops"]
    t_mem = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"decode_attn_roofline: {calls} kernel calls, {t!r} s; bound by "
          f"{'bytes' if t_mem >= t_ops else 'operations'} "
          f"({nbytes} B, {ops} ops)", file=sys.stderr)
    return 100.0 * max(t_ops, t_mem) / t
