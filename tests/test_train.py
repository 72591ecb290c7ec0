"""Train layer: sharded state, train step convergence, checkpoint, controller."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import ray_tpu
from ray_tpu.models import get_config
from ray_tpu.parallel import MeshSpec, build_mesh, default_rules
from ray_tpu.train import (
    CheckpointConfig,
    FailureConfig,
    LMTrainer,
    Result,
    RunConfig,
    RunStatus,
    ScalingConfig,
    Trainer,
    create_train_state,
    default_optimizer,
    make_train_step,
)


@pytest.fixture
def mesh8():
    return build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))


def _batches(key, n, batch, seq, vocab):
    for i in range(n):
        key, sub = jax.random.split(key)
        yield {"tokens": jax.random.randint(sub, (batch, seq + 1), 0, vocab)}


def test_state_shardings_cover_optimizer_moments(mesh8):
    config = get_config("llama-tiny")
    opt = default_optimizer(1e-3, total_steps=10)
    state, shardings = create_train_state(
        config, opt, jax.random.PRNGKey(0), mesh8, default_rules()
    )
    # adam mu/nu must inherit the param specs (fsdp/tp), not be replicated
    mu = state.opt_state[1][0].mu
    assert mu["blocks"]["w_up"].sharding.spec == PartitionSpec(None, "fsdp", "tp")
    assert state.params["blocks"]["w_up"].sharding.spec == PartitionSpec(None, "fsdp", "tp")
    # scalars replicated
    assert state.step.sharding.spec == PartitionSpec()


def test_train_step_reduces_loss(mesh8):
    config = get_config("gpt2-tiny")
    opt = default_optimizer(1e-2, warmup_steps=2, total_steps=40)
    state, shardings = create_train_state(
        config, opt, jax.random.PRNGKey(0), mesh8, default_rules()
    )
    step = make_train_step(config, opt, mesh8, state_shardings=shardings)
    # one fixed batch: loss must drop when overfitting it
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, config.vocab_size)}
    losses = []
    for _ in range(30):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]
    assert int(state.step) == 30


def test_grad_accum_matches_big_batch(mesh8):
    config = get_config("gpt2-tiny")
    opt = default_optimizer(1e-3, total_steps=10)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 17), 0, config.vocab_size)

    state1, sh = create_train_state(config, opt, jax.random.PRNGKey(0), mesh8)
    step1 = make_train_step(config, opt, mesh8, state_shardings=sh)
    state1, m1 = step1(state1, {"tokens": tokens})

    state2, sh2 = create_train_state(config, opt, jax.random.PRNGKey(0), mesh8)
    step2 = make_train_step(config, opt, mesh8, state_shardings=sh2, grad_accum=2)
    state2, m2 = step2(state2, {"tokens": tokens})

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    p1 = jax.tree.leaves(state1.params)[0]
    p2 = jax.tree.leaves(state2.params)[0]
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-5)


def test_lm_trainer_with_checkpoint_resume(tmp_path, mesh8):
    config = get_config("gpt2-tiny")
    ckpt = CheckpointConfig(checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=5)
    trainer = LMTrainer(
        config,
        mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
        learning_rate=1e-3,
        total_steps=10,
        checkpoint_config=ckpt,
    )
    metrics = trainer.train(
        _batches(jax.random.PRNGKey(0), 10, 8, 16, config.vocab_size),
        num_steps=10,
        report_every=5,
    )
    assert metrics["step"] == 10
    assert metrics["tokens_per_sec"] > 0
    assert trainer.ckpt_mgr.latest_step() == 10

    # new trainer resumes from step 10
    trainer2 = LMTrainer(
        config,
        mesh_spec=MeshSpec(dp=2, fsdp=2, tp=2),
        learning_rate=1e-3,
        total_steps=10,
        checkpoint_config=ckpt,
    )
    restored = trainer2.maybe_restore()
    assert restored == 10
    p1 = jax.tree.leaves(trainer.state.params)[0]
    p2 = jax.tree.leaves(trainer2.state.params)[0]
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2))


def test_checkpoint_of_a_state_with_the_empty_ef_field_restores(tmp_path, mesh8):
    """Up to PR 28 `TrainState.ef` held the int8 gradient sync's error
    buffer, or None. A checkpoint written from such a state (the field
    empty) restores through CheckpointManager into today's TrainState with
    equal leaves. The field is kept for this alone: orbax records the
    empty entry, and refuses a target that has no such field."""
    import dataclasses
    from typing import Any

    from ray_tpu.train.checkpoint import CheckpointManager
    from ray_tpu.train.lm import TrainState

    @jax.tree_util.register_dataclass
    @dataclasses.dataclass
    class OldTrainState:
        step: jax.Array
        params: Any
        opt_state: Any
        rng: jax.Array
        ef: Any = None

    @jax.tree_util.register_dataclass
    @dataclasses.dataclass
    class StateWithoutEf:
        step: jax.Array
        params: Any
        opt_state: Any
        rng: jax.Array

    config = get_config("gpt2-tiny")
    opt = default_optimizer(1e-3, total_steps=10)
    state, sh = create_train_state(config, opt, jax.random.PRNGKey(3), mesh8)
    step = make_train_step(config, opt, mesh8, state_shardings=sh)
    batch = next(_batches(jax.random.PRNGKey(0), 1, 8, 16, config.vocab_size))
    state, _ = step(state, batch)   # step 1, moments no longer zero
    old = OldTrainState(state.step, state.params, state.opt_state, state.rng)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, old)
    mgr.wait_until_finished()

    target, _ = create_train_state(config, opt, jax.random.PRNGKey(4), mesh8)
    restored = CheckpointManager(str(tmp_path / "ckpt")).restore(target)
    assert isinstance(restored, TrainState) and int(restored.step) == 1
    want, got = jax.tree.leaves(state), jax.tree.leaves(restored)
    assert len(want) == len(got)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.sharding == b.sharding
    without = StateWithoutEf(target.step, target.params, target.opt_state, target.rng)
    with pytest.raises(ValueError, match="ef"):
        CheckpointManager(str(tmp_path / "ckpt")).restore(without)


def test_gang_trainer_reports_and_finishes(runtime):
    def loop(config):
        from ray_tpu import train

        ctx = train.get_context()
        for i in range(3):
            train.report({"step": i, "rank": ctx.world_rank})
        return "done"

    trainer = Trainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t1"),
        train_loop_config={},
    )
    result = trainer.fit()
    assert result.status == RunStatus.FINISHED
    assert len(result.metrics_history) == 3  # rank-0 reports only
    assert result.metrics["step"] == 2


def test_gang_trainer_failure_fast(runtime):
    def loop(config):
        from ray_tpu import train

        train.report({"step": 0})
        raise RuntimeError("worker exploded")

    trainer = Trainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t2", failure=FailureConfig(max_failures=0)),
        train_loop_config={},
    )
    result = trainer.fit()
    assert result.status == RunStatus.ERRORED
    assert "exploded" in result.error


def test_gang_trainer_restarts_then_succeeds(runtime, tmp_path):
    marker = tmp_path / "attempt"

    def loop(config):
        from ray_tpu import train

        n = int(marker.read_text()) if marker.exists() else 0
        marker.write_text(str(n + 1))
        if n == 0:
            raise RuntimeError("first attempt dies")
        train.report({"attempt": n})
        return "ok"

    trainer = Trainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t3", failure=FailureConfig(max_failures=2)),
        train_loop_config={},
    )
    result = trainer.fit()
    assert result.status == RunStatus.FINISHED
    assert result.num_restarts == 1
    assert result.metrics["attempt"] == 1


def test_elastic_gang_resizes_on_capacity(runtime):
    """Elastic scaling (reference v2 ScalingPolicy): with part of the
    cluster occupied the gang starts small; after capacity returns, the
    restart grows it back and training resumes from the checkpoint."""
    from ray_tpu.train import (
        FailureConfig, RunConfig, RunStatus, ScalingConfig, TrainController,
    )
    from ray_tpu.train.session import get_context, report

    @ray_tpu.remote
    class Blocker:
        def ping(self):
            return "ok"

    blockers = [Blocker.options(num_cpus=1).remote() for _ in range(5)]
    ray_tpu.get([b.ping.remote() for b in blockers], timeout=30)

    def train_fn(config=None):
        ctx = get_context()
        if ctx.world_size < 4:
            if ctx.world_rank == 0:
                for b in blockers:
                    ray_tpu.kill(b)  # capacity comes back
            report({"loss": 1.0}, checkpoint_step=5)
            raise RuntimeError("partial-capacity attempt dies")
        report({"loss": 0.5}, checkpoint_step=10)

    controller = TrainController(
        train_fn,
        ScalingConfig(num_workers=4, min_workers=1),
        RunConfig(name="elastic", failure=FailureConfig(max_failures=2)),
    )
    result = controller.run()
    assert result.status == RunStatus.FINISHED
    assert controller.world_sizes[0] < 4  # degraded start
    assert controller.world_sizes[-1] == 4  # grew back after restart
    assert result.checkpoint_step == 10
    assert result.num_restarts >= 1


# ------------------------------------------- what a recomputing step keeps

V5E_HBM = int(15.75 * 2 ** 30)


def _cell_step(monkeypatch, cell_config, mesh_spec, hbm_bytes, **changed):
    """(the step of a cell's configuration file, the shapes of its state):
    nothing is allocated, so the published widths cost nothing here."""
    import os

    from benchmark import model_config
    from ray_tpu.ops import losses
    from ray_tpu.train.lm import abstract_train_state

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = model_config.transformer_config(model_config.load_config(
        os.path.join(root, "benchmark", "configs", cell_config + ".json"))).replace(**changed)
    monkeypatch.setattr(losses, "device_hbm_bytes", lambda: hbm_bytes)
    mesh = build_mesh(mesh_spec, devices=jax.devices()[:mesh_spec.num_devices])
    opt = default_optimizer(3e-4, total_steps=1000)
    state, shardings = abstract_train_state(config, opt, mesh)
    return make_train_step(config, opt, mesh, state_shardings=shardings), state


MISTRAL = ("mistral-7b-v0.3-train-4chip", MeshSpec(fsdp=2, tp=2))
TRINITY = ("trinity-mini-train-1chip", MeshSpec())


MATMUL_NAMES = ("attn_residual", "mlp_up", "mlp_gate")
QKV = ("attn_q", "attn_k", "attn_v")    # the attention kernel's operands: one candidate, listed last (PR 62)
ATTN_OUT = ("attn_out", "attn_lse")   # the kernel's output and its lse: one candidate
LATENTS = ("attn_latent_q", "attn_latent_kv", "attn_latent_k_rope")   # a latent-attention layer's
ROUTING = ("moe_routing",)              # an expert layer's: logits, experts, scores, the sorted rows' order (PR 60)


@pytest.mark.parametrize("cell,mesh_spec,hbm,batch,seq,changed,want", [
    # every matmul output, as since PR 30. Not the attention output, which has room (0.41 GB
    # a device): at S = 1,024 the kernel's second run costs no more than keeping its
    # results moves (the chip: 38,334 tokens/s without it, 38,318 with it, PERF.md section 6).
    # The head, here and below: the fused one with the device's whole sequence as its one
    # chunk (12 x 1,024 x 16,384 logits a device; the chip, PR 28: -0.03% against dense)
    # Since PR 62 q, k and v behind them: 3,072 features a row where up and gate are 7,168 each, tried
    # last and ADDED to what fits before them (the chip: PERF.md section 6, PR 62)
    (*MISTRAL, V5E_HBM, 24, 1024, {}, ("selective", MATMUL_NAMES + QKV, 1024)),
    # beside two more layers of state one value as wide as up fits, not two (before PR 34's
    # refit, which leaves 6.5% of the chip free where it left 10%: the narrow residual alone);
    # q, k and v, less than half as wide, still do
    (*MISTRAL, V5E_HBM, 24, 1024, {"n_layers": 10}, ("selective", ("attn_residual", "mlp_up") + QKV, 1024)),
    # twelve layers: nothing before PR 54 (the whole-block program, not an out-of-memory error);
    # since then the blocks' inputs and the residual are half the rows a device (their sequences
    # lie over `tp`) and the narrow residual fits, and q, k and v beside it; with fourteen nothing does
    (*MISTRAL, V5E_HBM, 24, 1024, {"n_layers": 12}, ("selective", ("attn_residual",) + QKV, 1024)),
    (*MISTRAL, V5E_HBM, 24, 1024, {"n_layers": 14}, ("whole_block", (), 1024)),
    # twice the batch: the narrow residual (before the refit: nothing) and q, k and v; with ten layers
    # the residual alone since PR 54, with twelve nothing
    (*MISTRAL, V5E_HBM, 48, 1024, {}, ("selective", ("attn_residual",) + QKV, 1024)),
    (*MISTRAL, V5E_HBM, 48, 1024, {"n_layers": 10}, ("selective", ("attn_residual",), 1024)),
    (*MISTRAL, V5E_HBM, 48, 1024, {"n_layers": 12}, ("whole_block", (), 1024)),
    # a device of unknown size (the CPU): the step that fits wherever anything does
    (*MISTRAL, 0, 24, 1024, {}, ("whole_block", (), 0)),
    # the chip, PR 46: dense 143,573 tokens/s at 94.5%, 512 rows 149,090, the whole 1,024 149,395 at 80.2%
    ("gpt2-small-train-1chip", MeshSpec(), V5E_HBM, 24, 1024, {}, ("off", (), 1024)),
    # the whole sequence beside 32 rows' activations would run at the compiler's ceiling (140,453
    # at 94.8% against 148,833 with two chunks at 87.9%)
    ("gpt2-small-train-1chip", MeshSpec(), V5E_HBM, 32, 1024, {}, ("off", (), 512)),
    # 2,048 rows 85,961 at 76.3%, the whole 4,096 87,462 at 77.9%
    ("olmoe-1b-7b-train-1chip", MeshSpec(), V5E_HBM, 4, 4096, {}, ("off", (), 4096)),
    # a family that names no candidates is recomputed whole wherever it recomputes
    ("olmoe-1b-7b-train-1chip", MeshSpec(), 4 * V5E_HBM, 4, 4096, {"remat": True},
     ("whole_block", (), 4096)),
    # the mixed stack: the attention kernels' outputs, beside the fused head; since PR 60 the four expert
    # layers' routing and shared gate and up too (the unrolled run's: they go as its gradients come)
    (*TRINITY, V5E_HBM, 2, 8192, {}, ("selective", ROUTING + ATTN_OUT + ("moe_shared_gate", "moe_shared_up"), 8192)),
    (*TRINITY, V5E_HBM, 4, 8192, {}, ("whole_block", (), 8192)),
    (*TRINITY, 0, 2, 8192, {}, ("whole_block", (), 0)),
    ("smallthinker-21b-a3b-train-1chip", MeshSpec(), V5E_HBM, 1, 16384, {}, ("selective", ROUTING + ATTN_OUT, 16384)),
    # dense 27,956 tokens/s at 90.3%, the whole 8,192 27,938 at 89.3% (2,048: 27,833, 4,096: 27,709)
    # since PR 60 the routing's 25 MB come first and leave the latents 12 MB over the ceiling, the shared
    # expert's gate, worth the same a byte, 7 MB under it (the chip: 585.2 -> 577.7 ms a step, PERF.md section 6)
    ("glm-4.7-flash-train-1chip", MeshSpec(), V5E_HBM, 2, 8192, {},
     ("selective", ROUTING + ATTN_OUT + ("moe_shared_gate",), 8192)),
], ids=["mistral-2x2", "mistral-2x2-10-layers", "mistral-2x2-12-layers", "mistral-2x2-14-layers",
        "mistral-2x2-batch-48", "mistral-2x2-batch-48-10-layers", "mistral-2x2-batch-48-12-layers",
        "mistral-2x2-unknown-size", "gpt2s", "gpt2s-batch-32", "olmoe",
        "olmoe-with-remat", "trinity", "trinity-batch-4", "trinity-unknown-size", "smallthinker", "glm47flash"])
def test_remat_rule_on_the_cells_numbers(monkeypatch, cell, mesh_spec, hbm, batch, seq, changed, want):
    """`make_train_step(...)`'s `remat_plan_for(shape, state)` and
    `loss_chunk_for(shape, state)` at the shipped cells' widths, meshes and
    batches, on a v5e's 15.75 GiB. The Mistral cell keeps every named matmul
    output and leaves the stated share of the chip free; with ten layers or
    twice the batch fewer fit, with twelve or with both the narrow residual
    alone (half its rows a device since PR 54), with fourteen, or twelve at
    twice the batch, nothing does and the whole block is recomputed, as on a
    device whose size is unknown. The
    attention output is worth keeping at S = 8,192, not at 1,024. The Trinity cell
    keeps the attention kernels' outputs of its mixed stack: the two
    scanned dense layers' kept values are still held when every gradient
    exists, which is the moment that binds; twice the batch keeps nothing; an
    unknown size keeps nothing. The cells without `remat` recompute nothing.
    Every head on a device of known size is the fused one (since PR 46;
    each changed row carries the chip's reading), its chunk the largest
    candidate, the whole sequence first, with which the head's moment
    leaves 6.5% of the chip free: the whole sequence in every shipped
    cell, two chunks for gpt2-small at 32 rows; an unknown size keeps the
    dense head. The same numbers, the same plan: nothing live is read but
    the device's size."""
    from ray_tpu.ops import losses

    # the kernels' forms as the chip has them: a held expert layer's buffer (a candidate since PR 67) pads every
    # held expert's rows to the grouped matmuls' 256-row tile there and to one row elsewhere, and Trinity's has
    # room at the smaller size alone (tests/test_moe_buffer_remat.py)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, state = _cell_step(monkeypatch, cell, mesh_spec, hbm, **changed)
    plan = step.remat_plan_for((batch, seq + 1), state)
    assert (plan["remat"], plan["remat_saved"],
            step.loss_chunk_for((batch, seq + 1), state)) == want
    assert step.remat_plan_for((batch, seq + 1), state) is plan
    if want[0] == "off":
        assert plan["remat_recomputed_flops_share"] == 0.0 and plan["remat_saved_bytes"] == 0
    if cell == MISTRAL[0] and want[0] == "whole_block":
        # all of the block but the down projection: 319 + 8 of 436 + 8 MFLOP a token
        assert plan["remat_recomputed_flops_share"] == pytest.approx(0.736, abs=0.001)
    if cell.startswith("olmoe") and want[0] == "whole_block":
        assert plan["remat_recomputed_flops_share"] is None
    if cell == TRINITY[0] and want[0] == "whole_block":
        # a norm follows every sublayer's output: nothing of a block is spared
        assert plan["remat_recomputed_flops_share"] == 1.0
    if cell == TRINITY[0] and want[0] == "selective":
        # 16,384 rows x (32 heads of 128 + their lse, 4 B a head) x 2 B x 6 layers, and of the four expert
        # layers the routing (128 float32 logits, 8 choices x 12 B) and the shared expert's gate and up
        assert plan["remat_saved_bytes"] == 16384 * 2 * ((4096 + 64) * 6 + (304 + 2 * 1024) * 4)
        # all but the scores of five windowed layers and a full one and, since PR 60 (0.712 before), the four
        # expert layers' router and shared gate and up, of the stack's forward
        assert plan["remat_recomputed_flops_share"] == pytest.approx(0.668, abs=0.001)
    if want[:2] == ("selective", MATMUL_NAMES + QKV):
        # 12,288 rows a device x (its half of gate and of up + its half of the residual's
        # sequences, which lie over `tp` since PR 54 + its 16 + 4 + 4 heads of q, k and v) x 2 B x 8 layers
        assert plan["remat_saved_bytes"] == 12288 * (2 * 7168 + 4096 // 2 + 3072) * 2 * 8
        # what is left to recompute: the attention kernel (0.132 with q, k and v, before PR 62)
        assert plan["remat_recomputed_flops_share"] == pytest.approx(0.019, abs=0.001)
        # beside the whole-block step as the chip measured it (11.145 GB; the
        # estimate reads a little under), the stated share stays free
        assert 11.145e9 + plan["remat_saved_bytes"] < (1 - losses.HBM_FREE_FRACTION) * hbm
def test_remat_rule_keeps_in_order_of_recomputation_spared_a_byte():
    """The candidates one by one, not as a pair: with room for one d_ff-wide
    value, one is kept and the residual beside it; a spared all-reduce
    counts, so under tensor parallelism the residual can come first; a
    candidate is as large as the layers that write it."""
    from ray_tpu.models.transformer import RematCandidate
    from ray_tpu.ops.losses import HBM_FREE_FRACTION
    from ray_tpu.train.lm import _ALL_REDUCE_FLOPS_PER_BYTE, auto_remat_saved

    def held_bytes(cs):
        return sum(100 * sum(c.layers) * 2 * c.width for c in cs)

    def kept(candidates, room_rows):
        got, held = auto_remat_saved(
            candidates, rows=100, itemsize=2, peak_bytes=held_bytes,
            hbm_bytes=int(room_rows * 100 * 2 * 2 / (1 - HBM_FREE_FRACTION)) + 1)
        assert held == held_bytes(got)
        return tuple(name for c in got for name in c.names)

    up = RematCandidate(("mlp_up",), 64, 8192, 8192, False, (2,))
    gate = RematCandidate(("mlp_gate",), 64, 8192, 8192, False, (2,))
    residual = RematCandidate(("attn_residual",), 16, 512, 512, False, (2,))
    assert kept((up, gate, residual), 64 + 64 + 16) == ("mlp_up", "mlp_gate", "attn_residual")
    assert kept((up, gate, residual), 64 + 16) == ("mlp_up", "attn_residual")
    assert kept((up, gate, residual), 63) == ("attn_residual",)
    assert kept((up, gate, residual), 15) == ()
    assert auto_remat_saved((up, gate), rows=100, itemsize=2, peak_bytes=held_bytes,
                            hbm_bytes=0) == ((), 0)
    # 64 FLOPs a byte against 16 and an all-reduce
    exposed = residual._replace(tp_sum=True)
    assert _ALL_REDUCE_FLOPS_PER_BYTE > 48
    assert kept((up, gate, exposed), 64 + 16) == ("attn_residual", "mlp_up")
    # two names under one candidate are kept together or not at all, and a
    # candidate that one layer in two writes takes half the room
    pair = RematCandidate(("attn_out", "attn_lse"), 32, 8192, 8192, False, (1, 0))
    assert kept((pair, residual), 16 + 16) == ("attn_out", "attn_lse", "attn_residual")
    assert kept((pair, residual), 16 + 15) == ("attn_out", "attn_lse")
    # what costs as much to keep as it spares is left out though it fits
    assert kept((pair._replace(worth=0), residual), 16 + 16) == ("attn_residual",)


def test_step_peak_counts_a_scanned_run_whole_and_an_unrolled_run_by_its_larger_half():
    """The estimate's moments on a stack of a scanned run then an unrolled
    one (the Trinity cell's order): a value kept in the scanned run is still
    held when every gradient exists and adds its bytes; one kept in the
    unrolled run goes as that run's gradients come, so it is free while it is
    the smaller of the two; the head's moment counts the logits beside
    everything kept and no block's gradient."""
    from ray_tpu.models.transformer import RematCandidate
    from ray_tpu.train.lm import step_peak_bytes

    scanned = {"scanned": True, "gradients": 300, "inputs": 40, "block": 100, "period": 1, "layers": 2}
    unrolled = {"scanned": False, "gradients": 500, "inputs": 80, "block": 100, "period": 4, "layers": 4}

    def peak(kept, logits=0, runs=(scanned, unrolled)):
        return step_peak_bytes(kept, rows=10, itemsize=2, always=1000.0, logits=logits, runs=runs)

    # the scanned run's backward: the unrolled run's gradients, a block, its own gradients and inputs
    assert peak(()) == 1000 + 500 + 100 + 300 + 40
    assert peak((), logits=2000) == 1000 + 40 + 80 + 2000
    in_unrolled = RematCandidate(("a",), 5, 1, 1, False, (0, 4))     # 4 x 10 x 5 x 2 = 400 B
    in_scanned = RematCandidate(("b",), 5, 1, 1, False, (2, 0))      # 200 B
    in_both = RematCandidate(("c",), 5, 1, 1, False, (2, 4))
    assert peak((in_unrolled,)) == peak(())
    assert peak((in_scanned,)) == peak(()) + 200
    assert peak((in_both,)) == peak(()) + 200
    # past the run's gradients an unrolled run's kept values do count: its own backward
    # holds the scanned run's inputs, a block, and the larger of 500 and 2 x 400 + 80
    assert peak((in_unrolled, in_unrolled._replace(names=("d",)))) == max(
        peak(()), 1000 + 40 + 100 + 880)
    # one scan alone (the dense family): everything at once
    assert peak((in_scanned,), logits=150, runs=(scanned,)) == 1000 + 100 + 300 + 40 + 200


def test_step_peak_adds_the_other_layers_of_a_scanned_period():
    """A scan whose iteration runs `period` layers holds, in its backward
    pass, that iteration's own slices of gradients, kept values and inputs
    beside the stacked ones: one layer's are in `block`, the others' are
    added (the all-expert stack: 2 iterations of 4 layers)."""
    from ray_tpu.models.transformer import RematCandidate
    from ray_tpu.train.lm import step_peak_bytes

    run = {"scanned": True, "gradients": 800, "inputs": 160, "block": 100, "period": 4, "layers": 8}
    kept = RematCandidate(("a",), 5, 1, 1, False, (8,))             # 8 x 10 x 5 x 2 = 800 B

    def peak(kept, run):
        return step_peak_bytes(kept, rows=10, itemsize=2, always=1000.0, logits=0, runs=(run,))

    assert peak((), dict(run, period=1)) == 1000 + 100 + 800 + 160
    assert peak((), run) == 1000 + 100 + 960 + 960 * 3 / 8
    assert peak((kept,), run) == 1000 + 100 + 1760 + 1760 * 3 / 8


def test_selective_step_on_a_mesh_gives_one_devices_loss(monkeypatch):
    """fsdp=2 x tp=2 on virtual devices, the rule given room for every
    candidate: the step that keeps them reports what one device's
    whole-block step reports, and its plan counts a device's share (half
    the rows, half of gate and up and, its sequences lying over `tp` since
    PR 54, half of the residual)."""
    from ray_tpu.ops import losses

    config = get_config("llama-tiny").replace(remat=True)
    opt = default_optimizer(1e-3, total_steps=10)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, config.vocab_size)}

    def first_step(spec, hbm_bytes):
        monkeypatch.setattr(losses, "device_hbm_bytes", lambda: hbm_bytes)
        mesh = build_mesh(spec, devices=jax.devices()[:spec.num_devices])
        state, shardings = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
        step = make_train_step(config, opt, mesh, state_shardings=shardings)
        plan = step.remat_plan_for(batch["tokens"].shape, state)
        _, metrics = step(state, batch)
        return plan, {k: float(v) for k, v in metrics.items()}

    whole_plan, whole = first_step(MeshSpec(), 0)
    plan, sharded = first_step(MeshSpec(fsdp=2, tp=2), 10 ** 9)
    assert whole_plan["remat"] == "whole_block"
    assert plan["remat"] == "selective" and set(plan["remat_saved"]) == {
        "mlp_up", "mlp_gate", "attn_residual", *QKV}
    rows, layers, itemsize = 4 * 16, config.n_layers, jnp.dtype(config.dtype).itemsize
    # and its 2 + 1 + 1 heads of 16 of q, k and v
    assert plan["remat_saved_bytes"] == layers * rows * itemsize * (
        2 * config.d_ff // 2 + config.d_model // 2 + 4 * 16)
    assert sharded["loss"] == pytest.approx(whole["loss"], rel=2e-3)
    assert sharded["grad_norm"] == pytest.approx(whole["grad_norm"], rel=2e-2)
