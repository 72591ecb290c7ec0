"""Model FLOP/s utilization: operations the forward and backward passes
require per token (no recomputation; the family's own count where its
adapter has one, `model_config.train_flops_per_token`) x tokens per
second, over chips x the device's peak, in percent."""

from .. import model_config, roofline


def read(ctx):
    window = ctx.get("window")
    if not window or ctx["device"]["platform"] == "cpu":
        return None     # a utilization is a device number: a CPU rehearsal has none
    per_token = model_config.train_flops_per_token(ctx["conf"], ctx["traffic"]["seq"])
    rate = window["tokens"] / (window["t1"] - window["t0"])
    peak = roofline.peaks(ctx["device"]["kind"])["flops_per_s"]
    return 100.0 * per_token * rate / (ctx["conf"]["chips"] * peak)
