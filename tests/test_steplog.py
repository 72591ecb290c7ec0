"""Training forensics plane (train/steplog.py): per-rank step-level
timelines, exact-sum step-time decomposition, cross-rank skew.

The load-bearing drills:
- the exact-sum invariant: every SEALED sampled step's phase buckets
  sum exactly to its measured step wall time, by construction (the
  ``other`` seal is the remainder);
- sampling is opt-in and cheap: with the recorder off the module mark
  is a no-op and the trainer records nothing; with ``sample_every=N``
  only every N-th step pays the sync + marks;
- skew attribution: one rank's injected slow input pipeline makes the
  skew matrix AND the stall watchdog WARNING name that rank with
  dominant bucket ``data_wait``;
- marks federate into the GCS ``_steps`` table on the stats piggyback
  and the state queries join them cluster-wide with semantic dedup.
"""

import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu.core.config import cfg
from ray_tpu.models import get_config
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import LMTrainer, steplog


@pytest.fixture(autouse=True)
def _clean_steplog():
    steplog.log().clear()
    yield
    steplog.log().clear()
    cfg.reset()


def _batches(key, n, batch, seq, vocab):
    for _ in range(n):
        key, sub = jax.random.split(key)
        yield {"tokens": jax.random.randint(sub, (batch, seq + 1), 0, vocab)}


def _step_record(run, rank, step, *, data_wait=0.002, device=0.01,
                 ts=None):
    """A hand-built sampled-step record shaped like the trainer's
    `_steplog` payload entries."""
    buckets = {
        "data_wait": data_wait,
        "h2d": 0.001,
        "device": device,
        "ckpt_save": 0.0,
        "report": 0.001,
        "other": 0.0005,
    }
    return {
        "run": run, "rank": rank, "step": step,
        "node": None, "ts": time.time() if ts is None else ts,
        "wall_s": sum(buckets.values()), "buckets": buckets,
    }


# ------------------------------------------------------------ recorder core


def test_mark_records_both_clocks_and_seals_on_other():
    sl = steplog.StepLog()
    rec = sl.mark("data_wait", 0.25, run="r1", rank=0, step=3)
    assert rec["run"] == "r1" and rec["rank"] == 0 and rec["step"] == 3
    assert rec["phase"] == "data_wait" and rec["dur_s"] == 0.25
    assert rec["ts"] > 0 and rec["mono"] > 0 and rec["seq"] == 1
    # dup (run, rank, step, phase) dropped — what makes ingest idempotent
    assert sl.mark("data_wait", 0.99, run="r1", rank=0, step=3) is None
    (summary,) = sl.steps()
    assert summary["sealed"] is False and summary["wall_s"] is None
    sl.mark("device", 0.50, run="r1", rank=0, step=3)
    sl.mark("other", 0.05, run="r1", rank=0, step=3, wall_s=0.80)
    (summary,) = sl.steps()
    assert summary["sealed"] is True
    assert summary["wall_s"] == 0.80  # the seal's measured wall wins
    assert summary["buckets"]["other"] == 0.05
    # a seal WITHOUT wall_s: wall is the bucket sum by definition
    sl.mark("data_wait", 0.1, run="r1", rank=0, step=4)
    sl.mark("other", 0.2, run="r1", rank=0, step=4)
    s4 = next(s for s in sl.steps() if s["step"] == 4)
    assert s4["wall_s"] == pytest.approx(0.3)


def test_ring_and_index_eviction_and_since_cursor():
    sl = steplog.StepLog(mark_capacity=8, step_capacity=4)
    for i in range(20):
        sl.mark("data_wait", 0.01, run="r", rank=0, step=i)
    stats = sl.stats()
    assert stats["buffered_marks"] == 8
    assert stats["indexed_steps"] == 4
    assert stats["seq"] == 20
    assert {s["step"] for s in sl.steps()} == {16, 17, 18, 19}
    assert sl.timeline("r") and sl.timeline("r")[0]["step"] == 12
    batch = sl.since(0, max_n=3)
    assert [m["seq"] for m in batch] == [13, 14, 15]  # oldest-first walk
    rest = sl.since(batch[-1]["seq"], max_n=10)
    assert [m["seq"] for m in rest] == [16, 17, 18, 19, 20]
    assert sl.since(20) == []


def test_ingest_dedups_and_summarize_rebuilds():
    sl = steplog.StepLog()
    recs = [_step_record("fed", 0, 1), _step_record("fed", 1, 1,
                                                    data_wait=0.4)]
    accepted = sl.ingest(recs)
    assert len(accepted) == 2
    # the same records again (the in-process-gang double path): no-op
    assert sl.ingest(recs) == []
    summaries = sl.steps(run="fed")
    assert len(summaries) == 2 and all(s["sealed"] for s in summaries)
    for s in summaries:
        assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"])
    # a federated consumer rebuilds the same summaries from raw marks
    rebuilt = {(s["rank"], s["step"]): s
               for s in steplog.summarize_steps(sl.since(0))}
    assert rebuilt[(1, 1)]["buckets"]["data_wait"] == pytest.approx(0.4)
    assert rebuilt[(1, 1)]["sealed"] is True
    # malformed records are skipped, not fatal
    assert sl.ingest([{"run": "x"}, "not-a-dict", None]) == []


def test_module_mark_is_noop_when_disabled_and_registry_idempotent():
    before = steplog.log().stats()["seq"]
    cfg.set(train_step_log=False)
    try:
        assert not steplog.enabled()
        steplog.mark("data_wait", 0.1, run="dark", rank=0, step=1)
        assert steplog.log().stats()["seq"] == before
    finally:
        cfg.reset()
    assert steplog.enabled()
    steplog.mark("data_wait", 0.1, run="lit", rank=0, step=1)
    assert steplog.log().stats()["seq"] == before + 1
    steplog.register_step_phase("test.custom", "a drill phase")
    steplog.register_step_phase("test.custom", "overwrite ignored")
    assert steplog.step_phases()["test.custom"] == "a drill phase"
    del steplog.STEP_PHASES["test.custom"]
    assert steplog.SEAL_PHASE in steplog.STEP_PHASES


@pytest.mark.parametrize("wall", [0.5, 0.25], ids=["remainder", "rounded"])
def test_record_step_is_the_six_marks_and_the_reports_payload(wall):
    """The trainer hands a sampled step over in one call: the ring holds
    what six `mark` calls leave (the measured buckets in order, then the
    seal with the wall), the buckets sum to wall_s EXACTLY, and what
    comes back is the record a report carries under `_steplog`. A wall
    under the buckets' sum (float rounding) becomes the sum."""
    measured = {"data_wait": 0.125, "h2d": 0.0625, "device": 0.25,
                "ckpt_save": 0.0, "report": 0.03125}
    before = time.time()
    payload = steplog.record_step("rs-run", 3, 7, measured, wall)

    other = max(wall - sum(measured.values()), 0.0)
    want_wall = sum(measured.values()) + other
    reference = steplog.StepLog()
    ids = {"run": "rs-run", "rank": 3, "step": 7}
    for phase, dur in measured.items():
        reference.mark(phase, dur, **ids)
    reference.mark("other", other, wall_s=want_wall, **ids)

    def shape(marks):
        # seq counts on through the fixture's clear(): relative
        return [(m["seq"] - marks[0]["seq"], m["phase"], m["dur_s"],
                 m.get("attrs")) for m in marks]

    marks = steplog.log().timeline("rs-run")
    assert shape(marks) == shape(reference.timeline("rs-run"))
    assert [m["phase"] for m in marks] == list(steplog.STEP_PHASES)
    assert [set(m) for m in marks] == [set(m) for m in
                                       reference.timeline("rs-run")]
    (summary,), (ref_summary,) = steplog.log().steps(), reference.steps()
    assert {k: v for k, v in summary.items() if k != "ts"} \
        == {k: v for k, v in ref_summary.items() if k != "ts"}
    assert summary["sealed"] and summary["wall_s"] == want_wall
    assert sum(summary["buckets"].values()) == summary["wall_s"]  # exact

    assert list(payload) == ["run", "rank", "step", "node", "ts",
                             "wall_s", "buckets"]
    assert (payload["run"], payload["rank"], payload["step"]) \
        == ("rs-run", 3, 7)
    assert payload["node"] == summary["node"]
    assert before <= payload["ts"] <= time.time()
    assert payload["wall_s"] == want_wall
    assert payload["buckets"] == summary["buckets"]
    assert list(payload["buckets"]) == list(steplog.STEP_PHASES)
    # a controller that shares the ring ingests nothing twice; another
    # one rebuilds the same step from the payload alone
    assert steplog.log().ingest([payload]) == []
    assert steplog.StepLog().ingest([payload]) == [payload]
    # the recorder off: the payload still rides the report, no mark lands
    cfg.set(train_step_log=False)
    seq = steplog.log().stats()["seq"]
    dark = steplog.record_step("rs-dark", 0, 1, measured, wall)
    assert steplog.log().stats()["seq"] == seq
    assert dark["buckets"] == payload["buckets"]


# ------------------------------------------------- trainer instrumentation


def test_sampled_steps_exact_sum_sampling_gate_and_off_switch():
    """THE invariant: every sealed summary's buckets sum EXACTLY to the
    recorded step wall time (the seal is the remainder by construction;
    approx() covers float addition only). One trainer (one compile)
    drives three phases: sample_every=1, sample_every=4, recorder off."""
    cfg.set(step_log_sample_every=1)
    config = get_config("gpt2-tiny")
    trainer = LMTrainer(config, mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
                        learning_rate=1e-3, total_steps=24)
    trainer.train(
        _batches(jax.random.PRNGKey(0), 8, 8, 16, config.vocab_size),
        num_steps=8, report_every=4, run_name="exact-run",
    )
    summaries = steplog.log().steps(run="exact-run")
    assert len(summaries) == 8  # sample_every=1: every step decomposed
    for s in summaries:
        assert s["sealed"], s
        assert set(s["buckets"]) == set(steplog.STEP_PHASES)
        assert all(v >= 0.0 for v in s["buckets"].values()), s["buckets"]
        assert sum(s["buckets"].values()) == pytest.approx(
            s["wall_s"], rel=1e-9, abs=1e-12)
        # real work landed in the real buckets
        assert s["buckets"]["device"] > 0.0
    # nothing in the record is an estimate: every mark is a duration of
    # one of the trainer's spans (or their remainder)
    tl = steplog.log().timeline("exact-run")
    assert not any("estimated" in (m.get("attrs") or {}) for m in tl)

    # sampling gate: only every sample_every-th step is decomposed
    cfg.set(step_log_sample_every=4)
    trainer.train(
        _batches(jax.random.PRNGKey(1), 8, 8, 16, config.vocab_size),
        num_steps=8, report_every=4, run_name="sampled-run",
    )
    sampled = steplog.log().steps(run="sampled-run")
    assert len(sampled) == 2  # the trainer's dispatched steps 8 and 12

    # recorder off: the identical loop records NOTHING
    cfg.set(train_step_log=False)
    before = steplog.log().stats()["seq"]
    trainer.train(
        _batches(jax.random.PRNGKey(2), 8, 8, 16, config.vocab_size),
        num_steps=8, report_every=4, run_name="dark-run",
    )
    assert steplog.log().stats()["seq"] == before
    assert steplog.log().steps(run="dark-run") == []


# ------------------------------------------------------- skew + waterfall


def test_skew_matrix_and_dominant_bucket_name_the_slow_rank():
    sl = steplog.StepLog()
    sl.ingest([
        _step_record("skew", 0, 5, data_wait=0.002),
        _step_record("skew", 1, 5, data_wait=0.450),  # slow input pipe
        _step_record("skew", 0, 6),
    ])
    rows = steplog.skew_matrix(sl.steps(run="skew"))
    two_rank = next(r for r in rows if r["step"] == 5)
    assert two_rank["ranks"] == [0, 1]
    assert two_rank["straggler_rank"] == 1
    assert two_rank["dominant_bucket"] == "data_wait"
    assert two_rank["dominant_excess_s"] == pytest.approx(0.448)
    assert two_rank["spread_s"] == pytest.approx(0.448)
    single = next(r for r in rows if r["step"] == 6)
    assert single["ranks"] == [0] and single["straggler_rank"] == 0

    text = steplog.render_waterfall(sl.steps(run="skew"))
    lines = text.splitlines()
    assert "run skew" in lines[0] and "rank(s) 0,1" in lines[0]
    assert "legend:" in lines[1] and "d=data_wait" in lines[1]
    # one bar per (step, rank), Σ column proving the exact sum
    bars = [l for l in lines if "|" in l]
    assert len(bars) == 3
    for bar in bars:
        assert "wall" in bar and "Σ" in bar
    # the skew footer names the straggler + dominant bucket
    assert any("skew: straggler rank 1" in l
               and "dominant data_wait" in l for l in lines)
    assert steplog.render_waterfall([]) == "(no sampled steps)"


def test_straggler_drill_warning_names_rank_and_data_wait():
    """Acceptance: a gang whose rank 1 has an injected slow input
    pipeline. Its sampled-step records ride the report plane; when the
    stall fires, the watchdog WARNING names rank 1 AND the dominant
    bucket data_wait (fed by the controller's _observe_step_records)."""
    from ray_tpu import train
    from ray_tpu.train import RunConfig, ScalingConfig, TrainController
    from ray_tpu.util.events import events

    ray_tpu.init(num_cpus=8, detect_accelerators=False)
    cfg.set(train_stall_window_s=60.0,  # global window off the hot path
            train_stall_factor=4.0, train_stall_min_s=0.25,
            train_stall_ewma_alpha=0.3)
    run_name = "skew_drill"

    def stall_seen():
        return [e for e in events().list(severity="WARNING", source="watchdog", limit=200)
                if run_name in e["message"] and "STALLED" in e["message"]]

    def train_fn(config):
        import time as _t

        ctx = train.get_context()
        rank = ctx.world_rank
        slow = rank == 1
        for step in range(25):
            rec = {
                "run": "skew_drill", "rank": rank, "step": step,
                "node": None, "ts": _t.time(),
                "wall_s": 0.5 if slow else 0.02,
                "buckets": {
                    "data_wait": 0.45 if slow else 0.002,
                    "h2d": 0.001,
                    "device": 0.01,
                    "ckpt_save": 0.0, "report": 0.001,
                    "other": (0.5 - 0.462) if slow else (0.02 - 0.014),
                },
            }
            train.report({"step": step, "_steplog": [rec],
                          "_mono": _t.perf_counter()})
            if slow and step == 10:
                # the injected stall (EWMA regression): 1.2 s, and on a host
                # so loaded that the steps before it were slow too, until
                # the watchdog has seen it (the gang's workers are threads
                # of this process, so they read the same event log)
                _t.sleep(1.2)
                stalled = _t.monotonic()
                while not stall_seen() and _t.monotonic() - stalled < 20.0:
                    _t.sleep(0.05)
            else:
                _t.sleep(0.03)

    controller = TrainController(
        train_fn,
        ScalingConfig(num_workers=2, resources_per_worker={"CPU": 1.0}),
        RunConfig(name=run_name),
        train_config={},
        poll_interval=0.02,
    )
    result_box = {}
    t = threading.Thread(
        target=lambda: result_box.setdefault("result", controller.run()),
        daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 30
        warned = []
        while time.monotonic() < deadline and not warned:
            warned = stall_seen()
            time.sleep(0.02)
        assert warned, "stall watchdog never fired on the slow-input rank"
        msg = warned[0]["message"]
        assert "rank 1" in msg, msg
        assert "dominant bucket data_wait" in msg, msg
        assert warned[0].get("extra", {}).get("dominant_bucket") \
            == "data_wait"
        t.join(timeout=60)
        assert not t.is_alive()
        assert result_box["result"].status.value == "FINISHED", (
            result_box["result"].error
        )
        # the controller re-rang the gang's records: the skew matrix
        # over its steps names the same rank + bucket, every sampled step
        rows = steplog.skew_matrix(steplog.log().steps(run=run_name,
                                                       limit=1000))
        two_rank = [r for r in rows if len(r["ranks"]) == 2]
        assert two_rank, "no cross-rank step pairs reached the controller"
        assert all(r["straggler_rank"] == 1 for r in two_rank)
        assert all(r["dominant_bucket"] == "data_wait" for r in two_rank)
    finally:
        # a drill that failed leaves its gang running: the next tests of
        # this file start a runtime of their own and must not find this one
        t.join(timeout=60)
        ray_tpu.shutdown()


# ---------------------------------------------------------------- federation


def test_step_marks_federate_and_state_queries():
    from ray_tpu.core.gcs import STEPLOG_NS
    from ray_tpu.util import state

    rt = ray_tpu.init(num_cpus=1, head=True, detect_accelerators=False)
    try:
        ctx = rt.cluster
        my_hex = ctx.node_id.hex()
        steplog.log().ingest([
            _step_record("fed-run", 0, 1, data_wait=0.002),
            _step_record("fed-run", 1, 1, data_wait=0.300),
            _step_record("other-run", 0, 7),
        ])
        prev, tail = -1, []
        while len(tail) != prev:
            prev = len(tail)
            ctx._last_stats_ts = 0.0
            ctx._report_stats()
            tail = ctx.gcs.kv_get(my_hex, namespace=STEPLOG_NS) or []
        assert tail, "no marks federated into the _steps table"
        assert all(m.get("node") for m in tail)
        # cursor advanced: another pass without new marks is a no-op
        before = len(tail)
        ctx._last_stats_ts = 0.0
        ctx._report_stats()
        assert len(ctx.gcs.kv_get(my_hex, namespace=STEPLOG_NS)) == before
        # the state queries join local ring ∪ federated table with
        # SEMANTIC dedup (run, rank, step, phase)
        summaries = state.step_timeline("fed-run")
        assert [(s["rank"], s["step"]) for s in summaries] == [(0, 1), (1, 1)]
        for s in summaries:
            assert s["sealed"]
            assert sum(s["buckets"].values()) == pytest.approx(s["wall_s"])
        rows = state.list_steps()
        runs = {s["run"] for s in rows}
        assert {"fed-run", "other-run"} <= runs
        assert [s["run"] for s in state.list_steps(run="other-run")] \
            == ["other-run"]
        skew = state.step_skew("fed-run")
        assert skew and skew[0]["straggler_rank"] == 1
        assert skew[0]["dominant_bucket"] == "data_wait"
        # federation lag drains to zero once the cursor caught up
        assert ctx._federation_lag().get("steps", 0) == 0
        # a federated recorder off-switch: no new marks ship
        cfg.set(train_step_log=False)
        steplog.log().mark("data_wait", 0.1, run="dark-fed", rank=0, step=1)
        ctx._last_stats_ts = 0.0
        ctx._report_stats()
        assert not any(m["run"] == "dark-fed" for m in
                       ctx.gcs.kv_get(my_hex, namespace=STEPLOG_NS))
    finally:
        cfg.reset()
        ray_tpu.shutdown()
