"""ragged_paged_attention's share of its roofline over the traced span.

Required work: every prefill chunk the engine ingested in the span (the
request log's engine.prefill_chunk marks give offset and tokens) and every
decoded token that reached a client in it (token i > 0 of a request with
a P-token prompt attended to P + i positions), times the layers; the least
time the chip could take for it, over the kernel's device time in the
trace. Operations and bytes come from roofline.py."""

from .. import model_config, roofline
from ..trace_reduce import seconds_of


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("trace_t0") is None:
        return None
    kernel_s = seconds_of(trace, ["ragged_paged_attention"])
    if kernel_s <= 0:
        return None
    t0, t1 = ctx["trace_t0"], ctx["trace_t1"]
    shape = model_config.shape_numbers(ctx["conf"])
    sizes = dict(n_q_heads=shape["n_q_heads"], n_kv_heads=shape["n_kv_heads"],
                 head_dim=shape["head_dim"])
    total = {"ops": 0.0, "bytes": 0.0}

    def add(q_len, kv_len):
        cost = roofline.ragged_attention_cost(q_len=q_len, kv_len=kv_len, **sizes)
        total["ops"] += cost["ops"]
        total["bytes"] += cost["bytes"]

    for mark in ctx.get("marks", ()):
        if mark["phase"] == "engine.prefill_chunk" and t0 <= mark["mono"] < t1:
            attrs = mark.get("attrs") or {}
            add(attrs["tokens"], attrs["offset"] + attrs["tokens"])
    for record in ctx.get("records", ()):
        for i, t in enumerate(record.token_times):
            if i > 0 and t0 <= t < t1:
                add(1, record.prompt_tokens + i)
    least = roofline.roofline_seconds(total, ctx["device"]["kind"])["seconds"]
    return 100.0 * shape["n_layers"] * least / kernel_s
