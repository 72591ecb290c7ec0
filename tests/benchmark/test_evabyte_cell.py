"""What PR 51 adds to the benchmark for `train-evabyte-fsdp4-32k`: the
adapter's required work against a hand count, `eva_cost` against a brute
count, the shipped configuration against the catalog row's published keys, the
adapter's refusals by key, the new metric files and the reader, the cell's
place in BENCHMARK.json (subsets and names), and a CPU rehearsal of a tiny cell
of the family through the harness on a virtual mesh (dp=2 x fsdp=4) that ends
`correct`, with the interpreted kernels too, and does not with every weight
through float8_e4m3 or with the far part dropped."""

import json
import os
import re

import pytest

from bench_helpers import RESULT_KEYS, ROOT, float8_weights, load
from benchmark import eva_cost, model_config, roofline
from benchmark.readers import eva_attn_fwd_roofline, scope_busy_share

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_evabyte")
CELL = "train-evabyte-fsdp4-32k"
CONF = os.path.join(ROOT, "benchmark", "configs", "evabyte-6.5b-train-4chip.json")
NEW_METRICS = ("attn_eva_busy_share", "eva_far_busy_share", "head_multibyte_busy_share",
               "eva_attn_fwd_roofline", "eva_far_fwd_roofline")
# the catalog row `EvaByte` beside the model-configs guide: its `config`, every key
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
    "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
    "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768, "max_seq_length": 32768, "mixedp_attn": True,
    "model_type": "evabyte", "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None,
    "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def test_required_work_of_a_byte_by_hand():
    """At S = 3 windows by hand, then the cell's 32,768: a layer's forward is
    404.8 MFLOP of matmuls and 32.5 of scores (1,024.5 exact keys and 960
    summaries a query on average: 7.4% of the layer; full causal attention
    would be 268 MFLOP, 40%), the head 21 MFLOP once."""
    conf = model_config.load_config(CONF)
    layers = conf["num_hidden_layers"]
    m, heads, d, f, w, c = 4096, 32, 128, 11008, 2048, 16
    matmuls = 2 * (4 * m * heads * d + 3 * m * f)
    head = 2 * m * 8 * 320
    # three windows: a query of window W sees (w + 1) / 2 of its own keys on average and 128 W summaries
    s = 3 * w
    visible = (w + 1) / 2 + (w // c) * (0 + 1 + 2) / 3
    forward = layers * (matmuls + 4 * d * heads * visible + 8 * d * heads) + head
    assert model_config.train_flops_per_token(conf, s) == pytest.approx(3 * forward, rel=1e-12)
    s = 32768
    scores = 4 * d * heads * ((w + 1) / 2 + 128 * (s / w - 1) / 2)
    assert matmuls == pytest.approx(404.8e6, rel=1e-3) and scores == pytest.approx(32.5e6, rel=2e-3)
    assert scores / (matmuls + scores) == pytest.approx(0.074, abs=0.001)
    assert 4 * d * heads * s / 2 / (matmuls + 4 * d * heads * s / 2) == pytest.approx(0.40, abs=0.005)
    per_token = model_config.train_flops_per_token(conf, s)
    assert per_token == pytest.approx(3 * (layers * (matmuls + scores + 8 * d * heads) + head), rel=1e-12)
    assert per_token / layers == pytest.approx(1.32e9, rel=0.01)
    shape = model_config.shape_numbers(conf)
    assert (shape["n_q_heads"], shape["n_kv_heads"], shape["head_dim"], shape["vocab"]) == (32, 32, 128, 2560)
    assert model_config.expert_layer(conf) is None and model_config.attention_window(conf) is None
    assert model_config.adapter(conf).eva_layer(conf) == {
        "layers": layers, "heads": 32, "head_dim": 128, "window": 2048, "chunk": 16}


@pytest.mark.parametrize("seq, window, chunk", [(24, 8, 2), (32, 8, 4), (8, 8, 2), (64, 16, 2)])
def test_eva_cost_against_a_brute_count(seq, window, chunk):
    local = sum(1 for i in range(seq) for t in range(seq) if t // window == i // window and t <= i)
    far = sum(1 for i in range(seq) for j in range(seq // chunk) if (j * chunk) // window < i // window)
    assert eva_cost.visible_pairs(seq, window, chunk) == {"local": local, "far": far}
    cost = eva_cost.eva_fwd_cost(batch=3, seq=seq, heads=2, head_dim=16, window=window, chunk=chunk)
    assert cost["ops"] == 3 * 2 * (4 * 16 * (local + far) + 8 * 16 * seq)
    # q, k, v and the output once each, a summary key and value a chunk, bfloat16
    assert cost["bytes"] == 3 * 2 * 2 * 16 * (4 * seq + 2 * (seq // chunk))
    alone = eva_cost.eva_far_fwd_cost(batch=3, seq=seq, heads=2, head_dim=16, window=window, chunk=chunk)
    assert alone["ops"] == 3 * 2 * 4 * 16 * far
    assert alone["bytes"] == 3 * 2 * (2 * 16 * (3 * seq + 2 * (seq // chunk)) + 8 * seq)


def test_eva_cost_at_the_published_sizes():
    """One chip's sequence: 33.6 M local and 31.5 M far pairs a head, 1.07
    TFLOP a call, compute-bound on the v5e: 5.4 ms at peak; the far kernel's
    part 0.52 TFLOP, 2.6 ms."""
    pairs = eva_cost.visible_pairs(32768, 2048, 16)
    assert pairs == {"local": 16 * 2048 * 2049 // 2, "far": 2048 * 128 * 120}
    sizes = dict(batch=1, seq=32768, heads=32, head_dim=128, window=2048, chunk=16)
    least = roofline.roofline_seconds(eva_cost.eva_fwd_cost(**sizes), "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(5.42e-3, rel=0.01)
    far = roofline.roofline_seconds(eva_cost.eva_far_fwd_cost(**sizes), "TPU v5 lite")
    assert far["bound"] == "compute" and far["seconds"] == pytest.approx(2.62e-3, rel=0.01)


def test_configuration_carries_every_published_key():
    conf = load(CONF)
    differ = {k for k, v in CATALOG.items() if conf.get(k, "missing") != v}
    assert differ == {"num_hidden_layers"} == set(conf["reduced"])
    assert conf["published"] == {"num_hidden_layers": 32}
    assert conf["num_hidden_layers"] >= 4 and "share" not in conf       # the floor; every head and row held
    assert conf["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert conf["chips"] == 4 and conf["trainer"]["mesh"] == {"fsdp": 4}
    for group in ("deployment", "assumed", "departures", "sizing", "probe", "trainer"):
        assert conf[group], group
    assert {"pooling", "visibility", "pred_head_weights", "head_layout", "norms", "residual"} <= set(
        conf["assumed"]["why"])
    assert {"initialisation", "torch_dtype"} <= set(conf["departures"])
    assert conf["trainer"]["learning_rate"] == 3e-4 and conf["program"] == {"remat": True}
    mistral = load(os.path.join(ROOT, "benchmark", "configs", "mistral-7b-v0.3-train-4chip.json"))
    assert {k: v for k, v in conf["trainer"].items() if k != "mesh"} == {
        k: v for k, v in mistral["trainer"].items() if k != "mesh"}
    model_config.check_reduced(conf, "the shipped file")
    mc = model_config.transformer_config(conf)
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.kv_heads, mc.head_dim, mc.d_ff, mc.vocab_size) == (
        conf["num_hidden_layers"], 4096, 32, 32, 128, 11008, 320)
    assert (mc.eva_attention, mc.eva_window, mc.eva_chunk, mc.pred_heads, mc.rope_theta, mc.norm_eps) == (
        True, 2048, 16, 8, 100000.0, 1e-5)
    assert mc.norm_unit_offset and mc.residual_fp32 and mc.remat and not mc.tie_embeddings
    assert mc.max_seq == 32768 and mc.act == "swiglu" and not mc.use_bias
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import model_family

    assert mc.dtype == jnp.bfloat16 and mc.param_dtype == jnp.float32 and mc.stream_dtype == jnp.float32
    shapes = jax.eval_shape(lambda key: model_family(mc).init_params(mc, key), jax.random.PRNGKey(0))
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    total = mc.n_layers * layer + 320 * 4096 + 4096 * 2560 + 4096
    assert layer == pytest.approx(202.4e6, rel=1e-3)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == total
    assert shapes["lm_head"].shape == (4096, 2560) and shapes["blocks"]["eva_mu"].shape == (mc.n_layers, 32, 128)


@pytest.mark.parametrize("change, match", [
    ({"attention_class": "mha"}, "attention_class"), ({"num_key_value_heads": 8}, "num_key_value_heads"),
    ({"window_size": 2040}, "window_size"), ({"num_chunks": 128}, "num_chunks"),
    ({"attention_bias": True}, "attention_bias"), ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"fp32_ln": True}, "fp32_ln"), ({"norm_add_unit_offset": False}, "norm_add_unit_offset"),
    ({"fp32_skip_add": False}, "fp32_skip_add"), ({"fp32_logits": False}, "fp32_logits"),
    ({"hidden_act": "gelu"}, "hidden_act"), ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"num_pred_heads": 0}, "num_pred_heads")],
    ids=["another-attention", "grouped-keys", "window-not-whole-chunks", "a-chunk-count", "attention-bias",
         "tied-embeddings", "float32-norm-weights", "a-plain-norm", "a-bfloat16-add", "bfloat16-logits",
         "another-activation", "scaled-rotary", "no-head"])
def test_the_adapter_refuses_what_neither_program_nor_reference_runs(change, match):
    conf = dict(load(CONF), **change)
    with pytest.raises(ValueError, match=match):
        model_config.transformer_config(conf)
    with pytest.raises(ValueError, match=match):
        model_config.adapter(conf).reference_steps(conf, 4 * 32768)


def test_the_new_metric_files_and_what_they_read_without_a_trace():
    for name, scopes in (("attn_eva_busy_share", ["attn.eva"]),
                         ("eva_far_busy_share", ["attn.eva.far", "attn.eva.pool", "attn.eva.merge"]),
                         ("head_multibyte_busy_share", ["head.multibyte"])):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["source"],
                meta["moves"]) == ("scope_busy_share", {"scopes": scopes}, "train step", "%", "lower",
                                   "device_trace", "train_tokens_per_s")
        assert scope_busy_share.read({"trace": None}, **meta["args"]) is None
    for name, args in (("eva_attn_fwd_roofline", {}), ("eva_far_fwd_roofline", {"kernel": "eva_far_fwd"})):
        meta = load(os.path.join(ROOT, "benchmark", "metrics", name + ".json"))
        assert (meta["reader"], meta["args"], meta["layer"], meta["unit"], meta["better"], meta["moves"]) == (
            "eva_attn_fwd_roofline", args, "train kernels", "%", "higher", "train_tokens_per_s")
    from ray_tpu.util import profiling

    assert {"attn.eva", "attn.eva.pool", "attn.eva.local", "attn.eva.far", "attn.eva.merge",
            "head.multibyte"} <= set(profiling.STEP_SCOPES)
    conf = model_config.load_config(CONF)
    ctx = {"conf": conf, "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 4, "seq": 32768}}
    # a run without a trace, a program without the table or the kernel, a family without such a layer
    assert eva_attn_fwd_roofline.read(dict(ctx, trace=None)) is None
    assert eva_attn_fwd_roofline.read(dict(ctx, trace={"busy_s": 1.0, "program_seconds": {}})) is None
    assert eva_attn_fwd_roofline.read(dict(ctx, trace={"op_seconds": {}, "op_counts": {}}),
                                      kernel="eva_far_fwd") is None
    mistral = model_config.load_config(os.path.join(ROOT, "benchmark", "configs", "mistral-7b-v0.3-train-4chip.json"))
    assert eva_attn_fwd_roofline.read(dict(ctx, conf=mistral, trace={"program_seconds": {"jit_step": [1.0]}})) is None


def test_roofline_counts_executions_times_layers_over_the_forward_scopes_time(monkeypatch):
    """3 executions of the traced step x the layers x the least time of one
    forward call at ONE chip's sequence, over what the table places under
    `attn.eva` in the pass `fwd`; the recomputed and the backward time is not
    in it. The far kernel alone: every call of it, recomputed ones too."""
    from ray_tpu.util import profiling

    conf = model_config.load_config(CONF)
    layers = conf["num_hidden_layers"]
    fwd = (("steplog.fwd_bwd_compute", "attn.full", "attn.kernel", "attn.eva", "attn.eva.far"), "fwd", 0)
    table = {"eva_far_fwd": (fwd, (fwd[0], "recompute", 0)), "fusion.2": ((fwd[0][:4] + ("attn.eva.pool",), "fwd", 0),),
             "fusion.3": ((fwd[0], "bwd", 0),), "fusion.4": ((("steplog.fwd_bwd_compute", "mlp"), "fwd", 0),)}
    monkeypatch.setattr(profiling, "program_ops", lambda: {"jit_step_under_mesh": table})
    trace = {"program_seconds": {"jit_step_under_mesh": [3.0, 3.0, 3.0], "jit_other": [9.0]},
             "op_seconds": {"eva_far_fwd": 0.24, "fusion.2": 0.06, "fusion.3": 0.3, "fusion.4": 1.0},
             "op_counts": {"eva_far_fwd": 6 * layers, "fusion.2": 3 * layers, "fusion.3": 3 * layers,
                           "fusion.4": 3 * layers}}
    ctx = {"conf": conf, "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 4, "seq": 32768}, "trace": trace}
    sizes = dict(batch=1, seq=32768, heads=32, head_dim=128, window=2048, chunk=16)
    least = roofline.roofline_seconds(eva_cost.eva_fwd_cost(**sizes), "TPU v5 lite")["seconds"]
    # the kernel's seconds are split evenly between its forward and its recomputed instance
    assert eva_attn_fwd_roofline.read(ctx) == pytest.approx(100 * 3 * layers * least / (0.12 + 0.06), rel=1e-9)
    far = roofline.roofline_seconds(eva_cost.eva_far_fwd_cost(**sizes), "TPU v5 lite")["seconds"]
    assert eva_attn_fwd_roofline.read(ctx, kernel="eva_far_fwd") == pytest.approx(
        100 * 6 * layers * far / 0.24, rel=1e-9)


def test_no_reader_and_no_harness_file_names_this_familys_keys():
    names = ("window_size", "chunk_size", "num_pred_heads", "attention_class", "fp32_skip_add")
    for folder in ("readers", "kinds"):
        for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, "benchmark", folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert not [key for key in names if key in text], name
    # the reference imports nothing of the program and runs no kernel, no merge of partial softmaxes
    with open(os.path.join(ROOT, "benchmark", "reference", "evabyte_ref.py"), encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(ray_tpu|benchmark)", text, re.M)
    assert re.findall(r"^from (\S+) import", text, re.M) == ["__future__", "typing"]
    assert "jax.nn.softmax(jnp.where(visible" in text and "logsumexp" not in text and "pallas" not in text


def test_cell_joins_the_shared_metrics_by_name_and_adds_five(benchmark_json):
    reports = {m["name"] for group in ("end_to_end", "per_layer") for m in benchmark_json[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"train_tokens_per_s", "setup_s", "mfu", "data_wait_share", "flash_attn_busy_share",
            "collective_exposed_share", "peak_hbm_share_train", "compiles_in_window_train",
            "setup_train_init_s", "setup_compile_s", "setup_programs_built", "setup_cost_analysis_s",
            "setup_untraced_share", "host_turnaround_ms", "step_dispatch_p50_ms",
            "step_unscoped_busy_share", "recompute_busy_share", "head_busy_share", "optimizer_busy_share",
            "attn_proj_busy_share", "mlp_busy_share", *NEW_METRICS} <= reports
    # under `attn.kernel` all that is no flash kernel is the pooling and the far kernels, which
    # `eva_far_busy_share` reads under their own name: no layout work, so not that metric's cell;
    # one causal call at the traffic's 32,768 is sixteen times the pairs of sixteen windows: not that
    # reader's; no window band, no experts, no state-space mixer, no latents, no module
    assert not {m for m in reports if m.startswith(("attn_layout_", "flash_fwd_roofline", "flash_win_", "moe_",
                                                    "ssm_", "attn_latent_", "mtp_"))}
    cell = next(w for w in benchmark_json["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b-train-4chip", "lm-steps-host-batches-4x32k", 4)
    config = next(c for c in benchmark_json["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == load(CONF)["source"] and config["file"].endswith(cell["config"] + ".json")
    for name in NEW_METRICS:
        (metric,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_tokens_per_s"
    traffic = load(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert {k: traffic[k] for k in ("kind", "batch", "seq", "zipf_a", "prefetch", "segment_steps")} == {
        "kind": "lm_steps", "batch": 4, "seq": 32768, "zipf_a": 1.1, "prefetch": 4, "segment_steps": 5}
    four = sum(w["chips"] == 4 for w in benchmark_json["workloads"])
    assert four == 2 and 4 * four <= len(benchmark_json["workloads"])


def _tiny_bench(benchmark_json):
    return dict(benchmark_json, workloads=[
        {"name": CELL, "config": "tiny-evabyte-train", "traffic": "tiny-lm-steps", "chips": 4}])


@pytest.mark.parametrize("impl", [None, "pallas"], ids=["plain-masked-form", "interpreted-kernels"])
def test_tiny_evabyte_cell_rehearsal_ends_correct(benchmark_json, monkeypatch, impl):
    """The real cell's entries with a tiny tree behind them: LMTrainer on two
    EVA layers under dp=2 x fsdp=4 (window 8, chunk 2, 4 windows a sequence, 8
    heads of 40 bytes), its first two steps against evabyte_ref's softmax
    over the explicit list of keys, clip and AdamW; the traced line. With the
    kernels (interpreted, once a shard) as with the plain masked form."""
    from benchmark import run

    if impl:
        transformer_config = model_config.transformer_config
        monkeypatch.setattr(model_config, "transformer_config",
                            lambda conf: transformer_config(conf).replace(attn_impl=impl))
    result = json.loads(json.dumps(run.run_cell(
        _tiny_bench(benchmark_json), CELL, 2**31 + 51, 2.0, True, tree=TINY, require_tpu=False)))
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    checks = result["info"]["checks"]
    assert checks["loss_step1_gap"]["value"] < 1e-5          # float32 against float32
    assert checks["first_gradient_worst_leaf_difference"]["value"] < 1e-4
    assert checks["change_worst_leaf_gap"]["value"] < 1e-4
    assert checks["loss_last"] < checks["loss_first"]
    # counters and span readers answer on a CPU; the trace readers and `mfu` find no chip
    assert {"data_wait_share", "compiles_in_window_train", "setup_train_init_s", "setup_compile_s",
            "setup_programs_built", "host_turnaround_ms", "step_dispatch_p50_ms"} <= set(result["metrics"])
    assert not {*NEW_METRICS, "mfu"} & set(result["metrics"])
    assert result["metrics"]["compiles_in_window_train"]["value"] == 0
    assert result["device"]["platform"] == "cpu"


def test_tiny_evabyte_cell_with_every_weight_through_float8_is_not_correct(benchmark_json):
    from benchmark import run

    with float8_weights():
        result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 51, 1.0, False,
                              tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0      # the step that was timed is the one compared
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])


def test_tiny_evabyte_cell_without_the_summaries_is_not_correct(benchmark_json, monkeypatch):
    """The control that DROPS the far part: every window attends to itself
    alone (the summaries masked out of the one softmax). Not `correct`."""
    from benchmark import run
    from ray_tpu.ops import attention, eva

    def no_far(q, k, v, kbar, vbar, window, chunk, sm_scale):
        b, h, s, d = q.shape
        fold = lambda x: x.reshape(b, h * (s // window), window, d)      # noqa: E731
        return attention.mha_reference(fold(q), fold(k), fold(v), causal=True, sm_scale=sm_scale).reshape(q.shape)

    monkeypatch.setattr(eva, "_eva_xla", no_far)
    result = run.run_cell(_tiny_bench(benchmark_json), CELL, 2**31 + 51, 1.0, False,
                          tree=TINY, require_tpu=False)
    assert result["correct"] is False
    checks = result["info"]["checks"]
    assert checks["first_loss_repeat_gap"]["value"] == 0.0
    assert (checks["first_gradient_worst_leaf_difference"]["value"]
            > checks["first_gradient_worst_leaf_difference"]["limit"])


def _tiny_first_step(keep):
    """The tiny cell's first step by the comparison's own two halves
    (check.program_first_steps, or with `keep` None train_ref.follow): {leaf:
    its first gradient} for the leaves `eva_mu` and `eva_phi`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import check
    from benchmark.kinds.lm_steps import TrainSystem
    from benchmark.reference import train_ref
    from benchmark.traffic import lm_batches

    conf = model_config.load_config(os.path.join(TINY, "configs", "tiny-evabyte-train.json"))
    traffic = load(os.path.join(TINY, "traffic", "tiny-lm-steps.json"))
    seed = 2**31 + 51
    batches = [next(lm_batches(traffic, seed, conf["vocab_size"]))]
    system = TrainSystem(conf, traffic, seed)
    try:
        program = check.program_first_steps(system, conf, batches)
    finally:
        system.close()
    pooling = lambda leaves: {name: np.asarray(leaf, np.float64)         # noqa: E731
                              for name, leaf in zip(program["leaf_names"], leaves) if "eva_" in name}
    if keep == "program":
        return pooling(program["first_gradient"])
    found = {}
    tokens = [jnp.asarray(b["tokens"]) for b in batches]
    train_ref.follow(program["seeded_params"], tokens, conf["trainer"],
                     first_gradient_seen=lambda g: found.update(pooling(jax.tree.leaves(g))),
                     **model_config.adapter(conf).reference_steps(
                         conf, int(tokens[0].shape[0] * (tokens[0].shape[1] - 1))))
    return found


@pytest.fixture(scope="module")
def reference_pooling_gradients():
    return _tiny_first_step("reference")


@pytest.mark.parametrize("fault, wrong", [(None, ()), ("mu-halved", ("eva_mu",)), ("phi-dropped", ("eva_phi",))],
                         ids=["sound", "mu's-gradient-halved", "phi's-gradient-dropped"])
def test_the_pooling_vectors_own_gradients_are_the_references(monkeypatch, reference_pooling_gradients,
                                                              fault, wrong):
    """REVIEW of PR 51: on the chip the worst gradient leaf is `w_gate` on every
    seed, and the cell's reading holds a leaf against the larger of its own norm
    and the median leaf's, which the pooling vectors' gradients are far under:
    the cell's comparison does not show a wrong pooling-vector gradient. Here, in
    float32, against the reference's OWN norm of the leaf: each pooling leaf's
    gradient is the reference's, and one that is halved or dropped reads half
    or all of its norm."""
    import jax
    import numpy as np

    from ray_tpu.ops import eva

    if fault:
        pool = eva.pool_chunks
        stop = jax.lax.stop_gradient

        def faulty(k, v, mu, phi, **kw):
            if fault == "mu-halved":
                mu = 0.5 * mu + stop(0.5 * mu)
            else:
                phi = stop(phi)
            return pool(k, v, mu, phi, **kw)

        monkeypatch.setattr(eva, "pool_chunks", faulty)
    ours = _tiny_first_step("program")
    assert sorted(ours) == sorted(reference_pooling_gradients) == ["['blocks']['eva_mu']", "['blocks']['eva_phi']"]
    for name, theirs in reference_pooling_gradients.items():
        gap = float(np.linalg.norm(ours[name] - theirs) / np.linalg.norm(theirs))
        if any(leaf in name for leaf in wrong):
            assert gap == pytest.approx(0.5 if fault == "mu-halved" else 1.0, abs=1e-3), (name, gap)
        else:
            assert gap < 1e-5, (name, gap)
