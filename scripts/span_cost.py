"""What the span record costs per training step, on the host alone: the
span calls of `LMTrainer.train`'s loop body (one `train.step` with its
three leaves; every tenth step a `train.report` with its three) around
no work, with the recorder on and with `trace_sample_ratio` 0.

    python scripts/span_cost.py [steps]

One JSON line: microseconds per step for each setting. A host number:
it says nothing about the device.
"""

import json
import sys
import time


def loop(steps: int) -> float:
    from ray_tpu.util import tracing

    tracer = tracing.tracer()
    loop_span = tracer.start_span("train.loop")
    ctx = loop_span.context
    t0 = time.perf_counter()
    for i in range(steps):
        step = tracer.start_span("train.step", parent=ctx)
        sctx = step.context
        with tracing.span("train.step.data_wait", parent=sctx, start=step.started) as a:
            pass
        with tracing.span("train.step.h2d", parent=sctx, start=a.ended) as b:
            pass
        with tracing.span("train.step.dispatch", parent=sctx, start=b.ended):
            pass
        if i % 10 == 9:
            report = tracer.start_span("train.report", parent=sctx)
            rctx = report.context
            with tracing.span("train.report.read", parent=rctx, start=report.started) as c:
                pass
            with tracing.span("train.report.cost", parent=rctx, start=c.ended) as d:
                pass
            with tracing.span("train.report.publish", parent=rctx, start=d.ended):
                pass
            report.end()
        step.end()
    elapsed = time.perf_counter() - t0
    loop_span.end()
    return elapsed / steps * 1e6


def main() -> int:
    import jax  # noqa: F401 - the spans mirror into the profiler only once JAX is there

    from ray_tpu.core.config import cfg

    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 20000
    loop(1000)
    out = {"steps": steps, "platform": jax.devices()[0].platform}
    out["on_us_per_step"] = min(loop(steps) for _ in range(3))
    cfg.set(trace_sample_ratio=0.0)
    out["off_us_per_step"] = min(loop(steps) for _ in range(3))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
