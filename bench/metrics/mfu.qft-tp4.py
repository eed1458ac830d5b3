"""Model FLOP/s utilization of the tensor-parallel QFT finetune: the
operations the loss needs per step (bench/flops.qft_step; no remat
recomputation, no lm_head when its logits are unused) times steps over the
traced window, over chips x the bf16 peak.  As ``mfu.qft``, on the cell's
chips."""


def read(ctx):
    n = ctx["counts"]
    if not n.get("steps"):
        return None
    ops = ctx["flops"].qft_step(n["dims"], n["batch"], n["seq_len"],
                                n["ce_proportion"]) * n["steps"]
    return 100.0 * ops / n["window_s"] / (ctx["chips"]
                                          * ctx["peaks"]["bf16_flops"])
