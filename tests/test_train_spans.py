"""The one span record on the training path (util/tracing + LMTrainer):
set-up, compiles and the step loop's host phases as spans with both
clocks, mirrored into the profile; the step log reads the same stamps.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.core.config import cfg
from ray_tpu.models import get_config
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import LMTrainer, steplog
from ray_tpu.util import tracing
from ray_tpu.util.goodput import BUCKETS, GoodputAccountant

LEAVES = {
    "train.loop": {"train.step", "train.ckpt_save", "train.drain"},
    "train.step": {"train.step.data_wait", "train.step.h2d",
                   "train.step.dispatch", "train.step.sync", "train.report",
                   "train.ckpt_save"},
    "train.report": {"train.report.read", "train.report.cost",
                     "train.report.ops", "train.report.publish"},
    "train.init": {"train.init.mesh", "train.init.state", "train.init.step_fn"},
}


@pytest.fixture(autouse=True)
def _clean():
    tracing.tracer().clear()
    steplog.log().clear()
    yield
    cfg.reset()
    tracing.tracer().clear()
    steplog.log().clear()


def _batches(seed, n, vocab, batch=8, seq=16):
    key = jax.random.PRNGKey(seed)
    for _ in range(n):
        key, sub = jax.random.split(key)
        yield {"tokens": jax.random.randint(sub, (batch, seq + 1), 0, vocab)}


@pytest.fixture(scope="module")
def trainer():
    """One tiny trainer (one compile of the step) for the whole file; its
    first two steps are run here, so that every test sees a warm one."""
    tracing.tracer().clear()
    config = get_config("gpt2-tiny")
    trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
                        learning_rate=1e-3, total_steps=200)
    init_spans = tracing.tracer().spans()
    first = trainer.train(_batches(0, 2, config.vocab_size), num_steps=2,
                          report_every=2)
    return trainer, config, init_spans, first


def _children(spans, parent):
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def _one(spans, name):
    (found,) = [s for s in spans if s["name"] == name]
    return found


def test_step_fn_span_names_the_attention_the_step_runs(trainer, monkeypatch):
    """`train.init.step_fn` carries the resolved attention implementation
    and how far the kernels' sub-tile walk engages, written when the first
    batch gives the sequence length (the step is traced per shape)."""
    trainer, _, init_spans, _ = trainer
    assert "attention_impl" not in _one(init_spans, "train.init.step_fn")["attrs"]
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert attrs == {"attention_impl": "xla", "attn_subtiles_visited": 0,
                     "attn_subtiles_masked": 0, "attn_subtiles_total": 0,
                     "attn_grid_steps": 0, "attn_grid_steps_live": 0, "attn_bwd_kernels": 0,
                     "loss_chunk": 0,   # 0: the dense head; no MoE key on a dense model
                     # gpt2-tiny keeps every activation: nothing is recomputed
                     "remat": "off", "remat_saved": (), "remat_saved_bytes": 0,
                     "remat_saved_by_run": (), "remat_saved_bytes_by_run": (),
                     "remat_recomputed_flops_share": 0.0}
    # on the chip, at the cells' sequence length
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer._note_step_plan((2, 1025))
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert attrs["attention_impl"] == "pallas"
    assert (attrs["attn_subtiles_visited"], attrs["attn_subtiles_masked"],
            attrs["attn_subtiles_total"]) == (10, 4, 16)
    assert (attrs["attn_grid_steps"], attrs["attn_grid_steps_live"]) == (1, 1)
    # one backward kernel a call, a head's float32 dQ resident in it
    assert attrs["attn_bwd_kernels"] == 1
    assert attrs["attn_bwd_resident_bytes"] == 1024 * trainer.config.head_dim * 4
    # past one tile a head the grid holds the live tiles alone, masked on the diagonal
    trainer._note_step_plan((2, 4097))
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert attrs["attn_grid_steps"] == attrs["attn_grid_steps_live"] == 10
    assert attrs["attn_subtiles_masked"] < attrs["attn_subtiles_visited"]
    trainer._note_step_plan((2, 17))


def test_step_fn_span_names_how_a_held_expert_layer_adds_up_a_tokens_rows(monkeypatch):
    """`moe_held_row_sum` follows the grouped matmuls' implementation, which
    is the one thing `_held_experts` asks: a gather and a scatter-add beside
    `ragged_dot`, the `moe_rows_sum` kernel beside the Pallas matmuls."""
    from test_mixed_stack import tiny

    trainer = LMTrainer(tiny(n_layers=4), mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2), total_steps=10)
    trainer._note_step_plan((8, 33))
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert (attrs["moe_impl"], attrs["moe_held_row_sum"]) == ("ragged_dot", "scatter_add")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer._note_step_plan((8, 33))
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert (attrs["moe_impl"], attrs["moe_held_row_sum"]) == ("gmm_pallas", "rows_sum_pallas")


@pytest.mark.parametrize("hbm_bytes,kernel_move_cost,want", [
    (0, None, ("whole_block", ())),                 # the CPU: a device of unknown size
    # at 16 tokens the attention kernel's output is worth less than keeping it moves
    (10 ** 9, None, ("selective", ("attn_residual", "mlp_up", "mlp_gate", "attn_q", "attn_k", "attn_v"))),
    # were keeping it free (as it all but is at the cells' 8,192), both its names follow
    (10 ** 9, 0, ("selective", ("attn_residual", "mlp_up", "mlp_gate", "attn_q", "attn_k", "attn_v",
                                "attn_out", "attn_lse"))),
], ids=["unknown-device-size", "room-for-every-name", "and-the-attention-output"])
def test_step_fn_span_names_what_a_recomputing_step_keeps(
        monkeypatch, hbm_bytes, kernel_move_cost, want):
    """`train.init.step_fn` of a model with `remat`: the six `remat*` keys
    (what is kept, as a whole and a run of the stack: here one scan), and
    what they report is what the step's trace gave the blocks' checkpoint."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import losses

    if kernel_move_cost is not None:
        monkeypatch.setattr(transformer, "_KEPT_KERNEL_FLOPS_PER_BYTE", kernel_move_cost)

    traced = []
    checkpoint_block = transformer.checkpoint_block
    monkeypatch.setattr(transformer, "checkpoint_block", lambda block_fn, saved=(): (
        traced.append(tuple(saved)), checkpoint_block(block_fn, saved))[1])
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: hbm_bytes)
    config = get_config("llama-tiny").replace(remat=True)
    trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2), total_steps=10)
    trainer.train(_batches(0, 1, config.vocab_size, batch=16), num_steps=1, report_every=1)
    attrs = trainer._step_fn_span.to_dict()["attrs"]
    assert (attrs["remat"], attrs["remat_saved"]) == want
    assert traced and set(traced) == {want[1]}
    assert attrs == {**attrs, **trainer.step_fn.remat_plan_for((16, 17), trainer.state)}
    rows, itemsize = 4 * 16, jnp.dtype(config.dtype).itemsize
    # a device's half of gate and up, (its sequences lie over `tp` since PR 54) of the residual
    # and of q, k and v (2 + 1 + 1 heads of 16); its half of the attention output with the lse of its 2 heads
    kept_width = (2 * config.d_ff // 2 + config.d_model // 2 + 4 * 16) if want[1] else 0
    if "attn_out" in want[1]:
        kept_width += config.d_model // 2 + 4 * 2 // itemsize
    assert attrs["remat_saved_bytes"] == config.n_layers * rows * itemsize * kept_width
    assert (attrs["remat_saved_by_run"], attrs["remat_saved_bytes_by_run"]) == (
        (want[1],), (attrs["remat_saved_bytes"],))
    # with the kernel's output and its operands both kept, no FLOP the costs count is run again
    assert ("attn_out" not in want[1]) == (attrs["remat_recomputed_flops_share"] > 0)
    assert attrs["remat_recomputed_flops_share"] < (0.3 if want[1] else 0.9)


def test_span_tree_of_a_three_step_train_call(trainer):
    trainer, config, _, _ = trainer
    trainer.train(_batches(1, 3, config.vocab_size), num_steps=3, report_every=3)
    spans = [s for s in tracing.tracer().spans() if s["name"].startswith("train.")]
    loop = _one(spans, "train.loop")
    assert loop["parent_id"] is None
    assert loop["attrs"]["num_steps"] == 3 and loop["attrs"]["steps"] == 3
    assert loop["attrs"]["run"] == "local" and loop["attrs"]["rank"] == 0
    assert {s["trace_id"] for s in spans} == {loop["trace_id"]}
    steps = _children(spans, loop)
    assert [s["name"] for s in steps] == ["train.step"] * 3
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        # both clocks, monotone, and every child inside its parent
        assert s["end_mono"] >= s["start_mono"] > 0 and s["end_ts"] >= s["start_ts"] > 0
        assert s["duration_s"] == pytest.approx(s["end_mono"] - s["start_mono"])
        parent = by_id.get(s["parent_id"])
        if parent is not None:
            assert parent["start_mono"] <= s["start_mono"]
            assert s["end_mono"] <= parent["end_mono"]
            assert s["name"] in LEAVES[parent["name"]]
    for step in steps:
        leaves = sorted(_children(spans, step), key=lambda s: s["start_mono"])
        names = [s["name"] for s in leaves]
        assert names[:3] == ["train.step.data_wait", "train.step.h2d",
                             "train.step.dispatch"]
        # one clock reading per boundary: a leaf starts where the one
        # before it ended, the first where the step started
        assert leaves[0]["start_mono"] == step["start_mono"]
        for before, after in zip(leaves, leaves[1:3]):
            assert after["start_mono"] == before["end_mono"]
            assert after["start_ts"] == before["end_ts"]
        # self time = duration less what the children cover
        self_s = step["duration_s"] - sum(s["duration_s"] for s in leaves)
        assert 0.0 <= self_s < step["duration_s"]
    # the report branch ran once, in the last step, with its three leaves
    report = _one(spans, "train.report")
    assert report["parent_id"] == steps[-1]["span_id"]
    assert [s["name"] for s in sorted(_children(spans, report),
                                      key=lambda s: s["start_mono"])] == [
        "train.report.read", "train.report.cost", "train.report.publish"]
    # budget: at most 8 spans a step and 4 a report
    assert len(spans) - 1 - 4 <= 8 * 3


def test_init_spans_and_the_first_report_carry_the_compiles(trainer):
    _, _, init_spans, first = trainer
    init = _one(init_spans, "train.init")
    assert {s["name"] for s in _children(init_spans, init)} == LEAVES["train.init"]
    state = _one(init_spans, "train.init.state")
    built = [s for s in init_spans if s["name"] == "compile.backend"
             and s["parent_id"] == state["span_id"]]
    assert built and all(s["attrs"]["fun_name"] for s in built)
    # what building the trainer and the first step compiled, nested traces
    # counted once: less than the wall time it happened in
    assert 0.0 < first["compile_s"] < init["duration_s"] + 120.0
    assert "dp_sync_s" not in first


@pytest.mark.parametrize("recompiles", [False, True])
def test_compile_spans_name_who_compiled(recompiles):
    fresh = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(5.0 if recompiles else 4.0)
    seconds0 = tracing.compile_seconds()
    with tracing.span("unit.caller") as caller:
        fresh(x)
    with tracing.span("unit.second_call"):
        fresh(x)
    if recompiles:
        with tracing.span("unit.new_shape") as again:
            fresh(jnp.arange(7.0))
    spans = tracing.tracer().spans()
    built = [s for s in spans if s["name"] == "compile.backend"
             and s["attrs"]["fun_name"] == "jit(<lambda>)"]
    parents = [caller] + ([again] if recompiles else [])
    assert [s["parent_id"] for s in built] == [p.span_id for p in parents]
    for s, parent in zip(built, parents):
        assert parent.start_mono <= s["start_mono"] <= s["end_mono"] <= parent.end_mono
        assert s["trace_id"] == parent.trace_id
    kinds = {s["name"] for s in spans if s["parent_id"] == caller.span_id}
    assert {"compile.trace", "compile.lower", "compile.backend"} <= kinds
    assert tracing.compile_seconds() > seconds0
    from ray_tpu.util.metrics import registry

    text = registry().prometheus_text()
    assert 'raytpu_compile_total{kind="backend"}' in text
    assert 'raytpu_compile_seconds_total{kind="trace"}' in text


def test_nested_traces_count_once_in_compile_seconds():
    """An inner event of a thread arrives before the one that contains
    it: the total grows by the union, the spans keep their own lengths."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    time.sleep(0.03)    # room for the two durations below, on this thread
    before = tracing.compile_seconds()
    tracing._on_jax_duration(event, 0.010, fun_name="inner")
    tracing._on_jax_duration(event, 0.025, fun_name="outer")
    assert tracing.compile_seconds() - before == pytest.approx(0.025, abs=1e-6)
    tracing._on_jax_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.001)
    tracing._on_jax_duration(
        "/jax/core/compile/backend_compile_duration", 0.002, fun_name="hit")
    tracing._on_jax_duration(
        "/jax/core/compile/backend_compile_duration", 0.003, fun_name="miss")
    names = {s["attrs"]["fun_name"]: s["name"] for s in tracing.tracer().spans()}
    assert names == {"inner": "compile.trace", "outer": "compile.trace",
                     "hit": "compile.cache_load", "miss": "compile.backend"}


def test_one_sampled_step_per_sample_every_across_calls(trainer):
    """Two train(num_steps=10) calls with step_log_sample_every=32 sync
    one step between them, not the first step of each call."""
    trainer, config, _, _ = trainer
    cfg.set(step_log_sample_every=32)
    trainer._steps_dispatched = 0
    for call in range(2):
        trainer.train(_batches(2 + call, 10, config.vocab_size), num_steps=10,
                      report_every=10, run_name="two-calls")
    assert trainer._steps_dispatched == 20
    assert len(steplog.log().steps(run="two-calls")) == 1
    spans = tracing.tracer().spans()
    assert [s["name"] for s in spans
            if s["name"] in ("train.drain", "train.step.sync")] == [
        "train.drain", "train.step.sync"]
    # four calls of ten cross step 32 once more
    for call in range(2):
        trainer.train(_batches(4 + call, 10, config.vocab_size), num_steps=10,
                      report_every=10, run_name="two-calls")
    assert len(steplog.log().steps(run="two-calls")) == 2


def test_step_log_buckets_are_the_spans_durations(trainer):
    trainer, config, _, _ = trainer
    cfg.set(step_log_sample_every=1)
    trainer.train(_batches(6, 2, config.vocab_size), num_steps=2,
                  report_every=2, run_name="same-stamps")
    spans = tracing.tracer().spans()
    steps = sorted((s for s in spans if s["name"] == "train.step"),
                   key=lambda s: s["start_mono"])
    summaries = steplog.log().steps(run="same-stamps")
    assert len(summaries) == len(steps) == 2
    for summary, step in zip(summaries, steps):
        leaves = {s["name"]: s["duration_s"] for s in _children(spans, step)}
        buckets = summary["buckets"]
        assert set(buckets) == {"data_wait", "h2d", "device", "ckpt_save",
                                "report", "other"}
        assert summary["wall_s"] == step["duration_s"]
        assert buckets["data_wait"] == leaves["train.step.data_wait"]
        assert buckets["h2d"] == leaves["train.step.h2d"]
        assert buckets["device"] == pytest.approx(
            leaves["train.step.dispatch"] + leaves["train.step.sync"])
        assert buckets["report"] == leaves.get("train.report", 0.0)
        assert sum(buckets.values()) == pytest.approx(summary["wall_s"], rel=1e-9)


def test_successive_calls_take_exactly_their_batches(trainer):
    """train(num_steps=n) takes n batches from a shared iterator: none is
    drawn and dropped at the end of a call."""
    trainer, config, _, _ = trainer
    source = _batches(8, 5, config.vocab_size)
    trainer.train(source, num_steps=2, report_every=2)
    trainer.train(source, num_steps=2, report_every=2)
    assert len(list(source)) == 1
    # an iterator that runs dry ends the call; the wait that found it
    # empty is on the record
    last = trainer.train(_batches(9, 1, config.vocab_size), num_steps=4,
                         report_every=1)
    assert last["step"] > 0
    dry = [s for s in tracing.tracer().spans() if s["attrs"].get("end_of_data")]
    assert [s["name"] for s in dry] == ["train.step"]


def test_a_report_reads_the_device_once(trainer, monkeypatch):
    """The device idles from the last step's end to the next dispatch, so
    the report fetches every scalar and the step counter in ONE host
    transfer (under a busy host each further round trip cost the OLMoE
    cell ~10 ms a segment, PERF.md PR 27): no scalar is read on its own."""
    trainer, config, _, _ = trainer
    cfg.set(train_step_log=False)       # a sampled step syncs on its own (an override: `_clean` resets it)
    fetched = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: fetched.append(x) or real(x))
    monkeypatch.setattr(
        type(jnp.zeros(())), "__float__",
        lambda self: pytest.fail("a scalar read on its own"))
    out = trainer.train(_batches(10, 4, config.vocab_size), num_steps=4,
                        report_every=2)
    assert len(fetched) == 2            # one a report
    metrics, step = fetched[-1]
    assert set(metrics) >= {"loss", "grad_norm", "num_tokens"}
    assert out["step"] == int(step) and isinstance(out["loss"], float)


def test_no_span_with_sample_ratio_zero_and_equal_metrics():
    config = get_config("gpt2-tiny")

    def run():
        trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
                            learning_rate=1e-3, total_steps=20, seed=3)
        return trainer.train(_batches(7, 3, config.vocab_size), num_steps=3,
                             report_every=3)

    traced = run()
    assert any(s["name"] == "train.step.dispatch" for s in tracing.tracer().spans())
    tracing.tracer().clear()
    cfg.set(trace_sample_ratio=0.0)
    dark = run()
    assert tracing.tracer().spans() == []
    for key in ("loss", "step", "input_wait_s", "ckpt_save_s", "compile_s"):
        assert key in dark
    assert dark["loss"] == traced["loss"] and dark["step"] == traced["step"]
    assert set(dark) == set(traced)
    # the spans are still the loop's clock when nothing records them
    assert dark["input_wait_s"] > 0.0 and dark["tokens_per_sec"] > 0.0


def test_spans_reach_the_profilers_host_plane(trainer, tmp_path):
    trainer, config, _, _ = trainer
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.train(_batches(10, 2, config.vocab_size), num_steps=2,
                      report_every=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = [e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    assert host.count("train.step.dispatch") == 2
    assert host.count("train.report.read") == 1
    # the structural parents stay out, so that a gap is named by a leaf
    assert not {"train.loop", "train.step", "train.report"} & set(host)


def test_tracing_stays_off_jax_until_somebody_imports_it():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.span('a') as sp:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        "d = tracing.tracer().spans()[0]\n"
        "assert d['end_mono'] >= d['start_mono'] > 0\n"
        "print('ok')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_recorded_wall_interval_lands_on_the_mono_clock_too():
    now = time.time()
    sp = tracing.tracer().record_span("unit.queue", now - 0.5, now - 0.2)
    assert sp.duration_s == pytest.approx(0.3, abs=1e-3)
    assert time.perf_counter() - sp.end_mono == pytest.approx(0.2, abs=0.05)
    exported = tracing.export_chrome_trace(tracing.tracer().spans())
    assert '"start_mono"' in exported and '"end_mono"' in exported


def test_goodput_takes_compile_s_and_has_no_estimated_bucket():
    assert "dp_sync" not in BUCKETS and "compile" in BUCKETS
    acct = GoodputAccountant("unit")
    acct.begin("init")
    time.sleep(0.05)
    acct.begin("step_compute")
    acct.observe_report_metrics({"compile_s": 0.02, "dp_sync_s": 9.0})
    acct.finish()
    buckets = acct.report(publish=False)["buckets"]
    assert buckets["compile"] == pytest.approx(0.02)
    assert sum(buckets.values()) == pytest.approx(acct.wall_time_s(), abs=1e-4)
