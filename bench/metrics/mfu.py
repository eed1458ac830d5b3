"""Model FLOP/s utilization of serving: the operations of the prompt
chunks and decoded tokens that the window's Engine.step calls computed
(bench/flops.prefill_chunk, decode_token; live lengths, no padding), over
the summed wall time of those calls x the bf16 peak."""


def read(ctx):
    steps = ctx["spans"].get("engine.step")
    ops = ctx["counts"].get("model_ops")
    if not steps or not ops:
        return None
    return 100.0 * ops / sum(steps) / (ctx["chips"]
                                       * ctx["peaks"]["bf16_flops"])
