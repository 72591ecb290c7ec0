"""Every shipped cell's configuration as the program sees it is what it was
before the sixth mixer kind, the list of kinds, the rotary full layer, the
tied head and the gates' epsilon became data (PR 61): the layers' kinds, the
parameter tree (every leaf's path, shape and dtype), its logical axes and the
plan the trainer reports, against tests/data/shipped_stacks.json, which was
recorded on PR 61's PARENT commit by the function below (the new cell's own
configuration is held by tests/benchmark/test_lfm2moe_cell.py). A change that
means to move one records it again and says so: PR 63 added the key
`kda_epilogue` ("xla" off a TPU) to `train-ling3flash-4k`'s plan, beside the
`kda_prologue` it reports, and nothing else of the record moved."""

import hashlib
import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "data", "shipped_stacks.json"), encoding="utf-8") as f:
    RECORDED = json.load(f)


def as_the_program_sees_it(config_name: str, batch: int, seq: int):
    from benchmark import model_config
    from ray_tpu.models import model_family

    mc = model_config.transformer_config(model_config.load_config(
        os.path.join(ROOT, "benchmark", "configs", config_name + ".json")))
    family = model_family(mc)
    shapes = jax.eval_shape(lambda key: family.init_params(mc, key), jax.random.PRNGKey(0))
    leaves = sorted((jax.tree_util.keystr(path), list(x.shape), str(x.dtype))
                    for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0])
    kinds = None
    if hasattr(mc, "layer_pattern"):
        from ray_tpu.models.mixed_stack import layer_kinds

        kinds = " ".join(kind.code for kind in layer_kinds(mc))
    return mc, "lm_head" in shapes, {
        "layer_kinds": kinds, "leaves": len(leaves),
        "parameters": sum(x.size for x in jax.tree.leaves(shapes)),
        "tree_sha256": hashlib.sha256(json.dumps(leaves).encode()).hexdigest(),
        "axes_sha256": hashlib.sha256(
            json.dumps(family.logical_axes(mc), sort_keys=True, default=list).encode()).hexdigest(),
        "plan": json.loads(json.dumps(family.plan(mc, batch, seq))),
    }


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_a_shipped_cell_builds_the_stack_tree_and_plan_it_did(cell):
    was = RECORDED[cell]
    mc, has_head, now = as_the_program_sees_it(was["config"], was["batch"], was["seq"])
    assert now == {name: was[name] for name in now}
    # and every new field is at the value that means "as before"
    assert getattr(mc, "route_norm_eps", 1e-9) == 1e-9
    assert not getattr(mc, "mixer_kinds", ()) and not getattr(mc, "attn_full_rope", False)
    assert has_head == (not mc.tie_embeddings)


def test_the_record_covers_the_nine_cells_shipped_before_this_one():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert set(RECORDED) == set(cells) - {"train-lfm2moe-8k"} and len(RECORDED) == 9
    assert sum(1 for was in RECORDED.values() if was["layer_kinds"]) == 5     # the mixed-stack cells before this one
