"""The main-path kernels at real widths, compiled for a DESCRIBED v5e chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (on-chip-measurement guide, section 2.3). Interpret
mode cannot see what it refuses: a pipelined flash kernel (deleted in PR 26)
passed every interpret test and was refused at D=64 (64-wide stream tile)
and in its backward (1-wide lse/delta tiles); a Mosaic call under GSPMD is
refused outright. Nothing runs here — a compile that passes is not a chip
run.

Steering is done in this file: code that asks `jax.default_backend()` sees
"tpu" through a monkeypatch, and Mosaic reads the described chip's kind
instead of the attached device's.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from ray_tpu.ops import attention as flash
from ray_tpu.ops.ragged_paged_attention import _ragged_pallas
from ray_tpu.parallel import MeshSpec, build_mesh


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture
def as_tpu(v5e, monkeypatch):
    """Take the TPU branches of the static rules, for the described chip."""
    import jax._src.pallas.mosaic.core as mosaic_core

    chip = v5e.devices[0]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mosaic_core, "get_device_kind", lambda: chip.device_kind)
    monkeypatch.setattr(mosaic_core, "get_num_device_cores", lambda: chip.num_cores)
    return chip


def _on(chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(chip))


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


GPT2 = ((24, 12, 1024, 64), (24, 12, 1024, 64))       # train-gpt2s
MISTRAL = ((12, 16, 1024, 128), (12, 4, 1024, 128))   # a train-mistral7b shard
LLAMA = ((4, 32, 2048, 128), (4, 8, 2048, 128))       # GQA 32 -> 8, 2 x 2 tiles
RING = ((4, 8, 2048, 128), (4, 8, 2048, 128))         # a ring-attention block


TRINITY = ((2, 32, 8192, 128), (2, 4, 8192, 128))     # train-trinity-mini-8k, GQA 32 -> 4
SMALLTHINKER = ((1, 28, 16384, 128), (1, 4, 16384, 128))  # train-smallthinker-16k, GQA 28 -> 4
OLMOE = ((4, 16, 4096, 128), (4, 16, 4096, 128))      # train-olmoe-64e-4k
GLM47FLASH = ((2, 20, 8192, 256), (2, 20, 8192, 256))  # train-glm47flash-8k: a wide head, MHA


@pytest.mark.parametrize(
    "shapes", [GPT2, MISTRAL, LLAMA, SMALLTHINKER, TRINITY, OLMOE, GLM47FLASH],
    ids=["gpt2-d64-s1024", "mistral-d128-s1024-gqa", "llama-d128-s2048-grid",
         "smallthinker-d128-s16384-gqa7", "trinity-d128-s8192-gqa8", "olmoe-d128-s4096",
         "glm47flash-d256-s8192"],
)
def test_flash_attention_fwd_and_grad_compile(as_tpu, monkeypatch, shapes):
    """One grid tile a head (the sub-tile walk, both cells' shapes) and a
    causal grid of live tiles in its two classes (2 x 2 tiles, and the three
    cells' full layers: 136, 36 and 10 steps a head; a head of 256 at the
    1,024-wide tile with the scoped VMEM it asks for: at the compiler's own
    16 MiB its backward kernel is refused), at the default blocks and the
    default rule. The backward is ONE kernel, and lowers within the scoped
    VMEM it asks for: a head's float32 dQ (8 MiB at 16,384 x 128 and at
    8,192 x 256, the tight ones) on top of the compiler's 16 MiB or a wide
    head's 64."""
    q_shape, kv_shape = shapes
    q, k, v = _on(as_tpu, q_shape), _on(as_tpu, kv_shape), _on(as_tpu, kv_shape)
    plan = flash.attention_plan(q_shape[2], head_dim=q_shape[3])
    assert plan["attention_impl"] == "pallas"
    assert plan["attn_grid_steps"] == plan["attn_grid_steps_live"]
    assert plan["attn_bwd_kernels"] == 1
    asked = {}
    call = flash.pl.pallas_call
    monkeypatch.setattr(flash.pl, "pallas_call", lambda *a, **kw: (
        asked.update({kw["name"]: getattr(kw.get("compiler_params"), "vmem_limit_bytes", None)}),
        call(*a, **kw))[1])

    def attend(q, k, v):
        return flash.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    forward = jax.jit(attend).lower(q, k, v).compile()
    assert _kernel_calls(forward) == 1 and "flash_fwd" in forward.as_text()
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
    assert _kernel_calls(grad) == 2  # the forward, the one backward kernel
    assert all(name in grad.as_text() for name in ("flash_fwd", "flash_bwd_dkv_dq"))
    assert "flash_win" not in grad.as_text() and "flash_bwd_dq" not in grad.as_text()
    tile_bytes = (64 if q_shape[3] > 128 else 16) * 2 ** 20
    assert plan["attn_bwd_resident_bytes"] == q_shape[2] * q_shape[3] * 4
    assert asked == {"flash_fwd": tile_bytes if q_shape[3] > 128 else None,
                     "flash_bwd_dkv_dq": tile_bytes + plan["attn_bwd_resident_bytes"]}


def _kernel_payloads(lowered_text: str) -> list:
    """sha256 of each Mosaic kernel of a lowered program, in order: its MLIR
    printed without debug info (the payload itself carries the checkout's
    path and the line of every frame above the kernel)."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    hashes = []
    for body in re.findall(r'"body": "([A-Za-z0-9+/=]+)"', lowered_text.replace("\\22", '"')):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True  # `stable_mosaic`, for a described chip
        with context:
            text = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
        hashes.append(hashlib.sha256(re.sub(r"loc\([^)]*\)", "", text).encode()).hexdigest()[:16])
    return hashes


# What the kernels of the calls that ISSUE 37's causal grid must NOT reach lowered
# to at its parent (183a4fe, jax 0.9.0): one tile a head. ISSUE 43's band classes
# must not reach those nor the causal grid's: the three cells' full layers as they
# lowered at ITS parent (b705cbf). The windowed bands' are the ones PR 43 recorded
# for its own kernels (`_BAND_WALK` as its sweep filled it; at b705cbf the
# 2,048 band lowered to 0c31b494380ff759, 355e5c6ff6c53256, 620890a7c0f71e85 and
# the 4,096 one to 74e2b245f31dcdf7, 8878514e319b7661, 17836ec746b727c5).
# A change that means to move them records its own: PR 45 made the backward ONE
# kernel, so each case's second hash is that kernel's as PR 45 measured it (at
# its parent, a5c1f2d, dK/dV and dQ lowered to c7d28a62531b4dfc + 0d9fd8c88998994e,
# 9f20af3abcff34e9 + 3861f3c4fd3b362a, 0d8aef9f814896c9 + 9b4b8ad6a4c59342,
# ea81818b8eb90d2c + 78314226a55998ae, 9b065628311428f4 + e01ec46875231a9b,
# 1cfe1a04b182bd14 + fc1d872126362468, 723cb52d86133fdc + f4ebf95525fd6754, in
# the order below); the forward's, the first, are those of PR 37 and PR 43 still.
_PARENT_KERNELS = {
    "gpt2-one-tile": (GPT2, None, ["168573c8a395eef5", "690b1522b695dc66"]),
    "mistral-one-tile": (MISTRAL, None, ["908c08fb4f6e79d1", "f9ba20ec880e4bac"]),
    "olmoe-causal-grid": (OLMOE, None, ["d2faf6bbdfcd3509", "222cc34eb56d05cd"]),
    "trinity-causal-grid": (TRINITY, None, ["f9110c423f8b7cea", "159f04a64eacc0ba"]),
    "smallthinker-causal-grid": (SMALLTHINKER, None, ["cc133df6a3900f80", "93842e428174dc08"]),
    "trinity-window-2048": (TRINITY, 2048, ["814711fc3c30193f", "a173a261c01c7e9e"]),
    "smallthinker-window-4096": (SMALLTHINKER, 4096, ["bd2bb6a8b593727a", "9323f427c2ca4473"]),
}


@pytest.mark.parametrize("case", sorted(_PARENT_KERNELS))
def test_one_tile_and_windowed_calls_keep_their_kernels(as_tpu, case):
    """`train-gpt2s` and `train-mistral7b-fsdp2tp2` (S = 1,024: one tile a
    head) run the kernels they ran before the causal grid, and the three
    other cells' full layers those of that grid as PR 43's parent lowered it:
    the forward lowers to the same MLIR. The windowed layers' forward hashes
    are PR 43's, which gave a band's tiles their classes, and every backward
    hash is PR 45's, which made the backward one kernel: they hold the cells'
    kernels to what those PRs measured."""
    (q_shape, kv_shape), window, want = _PARENT_KERNELS[case]
    q, k, v = _on(as_tpu, q_shape), _on(as_tpu, kv_shape), _on(as_tpu, kv_shape)
    tiles = q_shape[2] // 1024
    assert flash._live_grid(True, window, 1024, 1024, tiles, tiles) == ("causal-grid" in case)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)
    assert _kernel_payloads(lowered.as_text()) == want


@pytest.mark.parametrize("shapes,seq,window,block", [
    (TRINITY, 8192, 2048, None), (SMALLTHINKER, 16384, 4096, None), (TRINITY, 8192, 2048, 512),
    (TRINITY, 1024, 2048, None), (TRINITY, 1536, 1000, 512), (TRINITY, 4096, 1500, None)],
    ids=["cell-8k-band3", "cell-16k-band5", "block512-band5", "one-tile", "edge-inside-a-tile",
         "two-trailing-tiles"])
def test_windowed_flash_attention_fwd_and_grad_compile(as_tpu, shapes, seq, window, block):
    """The `flash_win_*` kernels of a windowed call (a band of tiles at static
    offsets from the diagonal, each computed whole or walked in sub-tiles by
    its class and `_BAND_WALK`) at both cells' shapes and widths: the forward,
    and the forward with the one backward kernel. A whole 1,024 x 1,024 piece
    beside a walked body and the head's resident dQ fits the scoped VMEM asked
    for."""
    (b, hq, _, d), (_, hkv, _, _) = shapes
    q = _on(as_tpu, (b, hq, seq, d))
    k = v = _on(as_tpu, (b, hkv, seq, d))

    def attend(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, window=window,
                                     block_q=block, block_kv=block)

    forward = jax.jit(attend).lower(q, k, v).compile()
    assert _kernel_calls(forward) == 1 and "flash_win_fwd" in forward.as_text()
    grad = jax.jit(jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))).lower(q, k, v).compile()
    assert _kernel_calls(grad) == 2
    assert "flash_win_bwd_dkv_dq" in grad.as_text() and "flash_win_bwd_dq" not in grad.as_text()


def test_default_rule_is_the_backend_alone(as_tpu, monkeypatch):
    """One kernel family since PR 26: the rule looks at the backend, not
    at the shape, and the walk engages at the cells' sequence length."""
    assert flash.resolve_attention_impl() == "pallas"
    assert flash.resolve_attention_impl("xla") == "xla"
    plan = flash.attention_plan(1024)
    assert (plan["attention_impl"], plan["attn_subtiles_visited"],
            plan["attn_subtiles_total"]) == ("pallas", 10, 16)
    with pytest.raises(ValueError, match="unknown attention implementation"):
        flash.resolve_attention_impl("pallas_pipelined")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert flash.resolve_attention_impl() == "xla"


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_ring_attention_fused_block_compiles(as_tpu, causal):
    """ops/ring_attention's fused local block, the forward with its lse:
    the diagonal block is causal, the blocks below it are not."""
    q = _on(as_tpu, RING[0])
    block = jax.jit(lambda q, k, v: flash._fwd_pallas(
        q, k, v, causal, 128 ** -0.5, 1024, 1024, 2048, False))
    assert _kernel_calls(block.lower(q, q, q).compile()) == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "region",
    [dict(lanes=4 + 8, rows=4 * 256 + 8 * 8, max_q_blocks=32),  # mixed tick
     dict(lanes=8, rows=8 * 8, max_q_blocks=1)],               # decode block
    ids=["prefill-chunks+decode", "decode-only"],
)
def test_ragged_paged_kernel_compiles(as_tpu, region, dtype):
    """Hq 32 / Hkv 8 / D 128 / page 64, block_q 8: the engine's shapes."""
    pages, max_pages = 4096, 16
    i32 = lambda *shape: _on(as_tpu, shape, jnp.int32)  # noqa: E731
    kernel = jax.jit(lambda *a: _ragged_pallas(
        *a, block_q=8, max_q_blocks=region["max_q_blocks"], interpret=False))
    compiled = kernel.lower(
        _on(as_tpu, (32, region["rows"], 128), dtype),
        _on(as_tpu, (8, pages, 64, 128), dtype),
        _on(as_tpu, (8, pages, 64, 128), dtype),
        *(i32(region["lanes"]) for _ in range(4)),
        i32(region["lanes"], max_pages),
    ).compile()
    assert _kernel_calls(compiled) == 1


def test_flash_attention_under_a_mesh_is_partitioned_per_shard(v5e, as_tpu):
    """GSPMD refuses a Mosaic call ("wrap the call in a shard_map"): traced
    under a context mesh, as make_train_step traces its step, the kernel
    runs per shard — batch over fsdp, heads over tp — on four chips."""
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=v5e.devices)
    spec = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), "tp", None, None))
    q = jax.ShapeDtypeStruct(LLAMA[0], jnp.bfloat16, sharding=spec)
    kv = jax.ShapeDtypeStruct(LLAMA[1], jnp.bfloat16, sharding=spec)

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            out = flash.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    assert _kernel_calls(compiled) == 2     # the forward, the one backward kernel
    # each chip holds a quarter of q: two of four sequences, half the heads
    per_chip = 2 * 16 * 2048 * 128 * 2
    assert compiled.memory_analysis().argument_size_in_bytes < 3 * per_chip
    no_context = jax.jit(lambda q, k, v: flash.flash_attention(q, k, v, causal=True))
    with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
        no_context.lower(q, kv, kv).compile()


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)], ids=["gate-up", "down"])
def test_moe_grouped_matmul_kernels_compile_at_the_olmoe_cell_shapes(as_tpu, k, n):
    """train-olmoe-64e-4k: 131,072 routed rows padded per expert to 256-row
    tiles (147,456 slots), 64 experts, 2048 <-> 1024: the forward alone,
    then it with both backward products (`moe_gmm_fwd`, `_dlhs`, `_drhs`)."""
    from ray_tpu.ops import grouped_matmul as gmm

    assert gmm.resolve_gmm_impl() == "pallas" and gmm.gmm_tile_rows() == gmm.PALLAS_TILE_ROWS
    slots = 131072 + 64 * gmm.PALLAS_TILE_ROWS
    lhs, rhs = _on(as_tpu, (slots, k)), _on(as_tpu, (64, k, n))
    sizes = _on(as_tpu, (64,), jnp.int32)

    def forward(lhs, rhs, sizes):
        return gmm.grouped_matmul(lhs, rhs, sizes, tile_rows=gmm.PALLAS_TILE_ROWS)

    def loss(lhs, rhs, sizes):
        return forward(lhs, rhs, sizes).astype(jnp.float32).sum()

    assert _kernel_calls(jax.jit(forward).lower(lhs, rhs, sizes).compile()) == 1
    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    # the forward is dead in this loss (a sum's gradient needs no output): dlhs, drhs
    assert _kernel_calls(grad.lower(lhs, rhs, sizes).compile()) == 2


@pytest.mark.parametrize("weighted,out_dtype", [(True, jnp.float32), (False, jnp.bfloat16)],
                         ids=["combine-f32", "dispatch-transpose-bf16"])
@pytest.mark.parametrize("slots,m,held", [(26624, 2560, 8), (36864, 2048, 16)],
                         ids=["smallthinker", "trinity"])
def test_moe_rows_sum_compiles_at_the_held_cells_shapes(as_tpu, slots, m, held, weighted, out_dtype):
    """`train-smallthinker-16k` (a pass's 26,624 slots of 2,560 in 8 groups)
    and `train-trinity-mini-8k` (36,864 of 2,048 in 16), 16,384 tokens each:
    the two signatures a step holds, bfloat16 rows with float32 gates summed
    to float32 (the combine) and bfloat16 rows with none to bfloat16 (the
    transpose of the dispatch's gather). One kernel call each."""
    from ray_tpu.ops import moe_rows_sum as rs

    tokens = 16384
    tiles = tokens // rs.rows_sum_tile(tokens, m)

    def summed(x, weight, token, bounds):
        return rs.rows_sum(x, weight if weighted else None, token, bounds, tokens, out_dtype=out_dtype)

    compiled = jax.jit(summed).lower(
        _on(as_tpu, (slots, m)), _on(as_tpu, (slots,), jnp.float32), _on(as_tpu, (slots,), jnp.int32),
        _on(as_tpu, (held, tiles + 1), jnp.int32)).compile()
    assert _kernel_calls(compiled) == 1 and "%moe_rows_sum" in compiled.as_text()


@pytest.mark.parametrize("batch, seq, heads, head_dim, groups, state", [(2, 8192, 64, 64, 8, 128), (1, 512, 8, 128, 4, 256)],
                         ids=["nemotron3nano-8-heads-of-64-a-group", "2-heads-of-128-a-group-state-256"])
def test_ssd_scan_kernels_compile_within_the_scoped_vmem_they_ask_for(as_tpu, batch, seq, heads, head_dim,
                                                                      groups, state):
    """`ssd_fwd` and `ssd_bwd` at the `train-nemotron3nano-8k` cell's shapes (a
    grid step: one chunk of a group's 8 heads, the (128, 512) float32 state
    in scratch) and at a wider head with a larger state: the rule says
    "pallas" there, a differentiated scan is the two calls, and Mosaic fits
    both in the `_VMEM_LIMIT` their calls ask for (a kernel that did not would
    be refused here as on the chip)."""
    from ray_tpu.ops import ssd

    sizes = dict(chunk=128, heads=heads, groups=groups, head_dim=head_dim, state=state)
    assert ssd.resolve_scan_impl(**sizes) == "pallas"
    assert ssd.scan_plan(seq, 128, heads=heads, groups=groups, head_dim=head_dim, state=state)[
        "ssm_scan_state_bytes"] == heads // groups * head_dim * state * 4 <= ssd._KERNEL_STATE_BYTES
    args = (_on(as_tpu, (batch, seq, heads, head_dim)), _on(as_tpu, (batch, seq, heads), jnp.float32),
            _on(as_tpu, (heads,), jnp.float32), _on(as_tpu, (batch, seq, groups, state)),
            _on(as_tpu, (batch, seq, groups, state)), _on(as_tpu, (heads,), jnp.float32))
    forward = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=128)).lower(*args)
    # the scoped VMEM the call asks Mosaic for, as the lowered call carries it
    assert f"\\22size\\22: {ssd._VMEM_LIMIT}}}]" in forward.as_text()
    assert _kernel_calls(forward.compile()) == 1
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=128).astype(jnp.float32) ** 2),
                            argnums=tuple(range(6)))).lower(*args).compile()
    assert _kernel_calls(grad) == 2


@pytest.mark.parametrize("batch, seq, inner, wide, groups", [(2, 8192, 4096, 10304, 8), (1, 400, 512, 512, 2)],
                         ids=["nemotron3nano-8-groups-of-512-in-the-projection", "2-groups-of-256-tiles-of-80-rows"])
def test_gate_norm_kernels_compile_within_the_scoped_vmem_they_ask_for(as_tpu, batch, seq, inner, wide, groups):
    """`ssm_gate_norm_fwd` and `ssm_gate_norm_bwd` at the `train-nemotron3nano-8k`
    cell's shapes (a grid step: 256 rows of all 8 groups, z read at the first
    4,096 features of the in-projection's (2, 8,192, 10,304) output) and at
    one other tiling: the rule says "pallas" there, a differentiated norm is
    the two calls, and Mosaic fits each in the scoped VMEM its call asks for
    (every tile twice and 8 MiB: a kernel that did not fit would be refused
    here as on the chip)."""
    from ray_tpu.ops import ssd

    rows = ssd._norm_rows(batch * seq)
    assert ssd.gate_norm_plan(batch * seq, inner, groups) == {"ssm_gate_norm_impl": "pallas", "ssm_gate_norm_rows": rows}
    args = (_on(as_tpu, (batch, seq, inner)), _on(as_tpu, (batch, seq, wide)), _on(as_tpu, (inner,), jnp.float32))
    norm = lambda *a: ssd.gated_group_norm(*a, groups=groups, eps=1e-5)     # noqa: E731
    forward = jax.jit(norm).lower(*args)
    assert f"\\22size\\22: {2 * 3 * rows * inner * 2 + 8 * 2**20}}}]" in forward.as_text()
    assert _kernel_calls(forward.compile()) == 1
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(norm(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2))).lower(*args)
    assert f"\\22size\\22: {2 * 5 * rows * inner * 2 + 8 * 2**20}}}]" in grad.as_text()
    assert _kernel_calls(grad.compile()) == 2


@pytest.mark.parametrize("batch, seq, wide, offset, channels, splits", [
    (2, 8192, 10304, 4096, 6144, (4096, 1024, 1024)), (1, 400, 384, 128, 256, None)],
    ids=["nemotron3nano-x-b-c-from-column-4096-of-the-projection", "tiles-of-80-rows-two-blocks-of-128"])
def test_conv_kernels_compile_within_the_scoped_vmem_they_ask_for(as_tpu, batch, seq, wide, offset, channels, splits):
    """`ssm_conv_fwd` and `ssm_conv_bwd` at the `train-nemotron3nano-8k` cell's
    shapes (a grid step: 256 rows of a sequence with all 6,144 channels, read
    as three blocks of 2,048 columns from column 4,096 of the in-projection's
    (2, 8,192, 10,304) output, with the strip of 16 rows before and, backward,
    after; x, B and C an output and a cotangent each) and at one other tiling: the rule says "pallas" there, a
    differentiated convolution is the two calls, and Mosaic fits each in the
    scoped VMEM its call asks for (every tile twice and 8 MiB)."""
    from ray_tpu.ops import ssd

    rows = ssd._conv_rows(seq)
    assert ssd.conv_plan(seq, channels, 4, offset, splits) == {"ssm_conv_impl": "pallas", "ssm_conv_rows": rows}
    args = (_on(as_tpu, (batch, seq, wide)), _on(as_tpu, (channels, 4), jnp.float32), _on(as_tpu, (channels,), jnp.float32))

    def conv(*a):
        out = ssd.causal_conv1d(*a, offset=offset, splits=splits)
        return out if splits is None else jnp.concatenate([part * (1 + i) for i, part in enumerate(out)], axis=-1)

    forward = jax.jit(conv).lower(*args)
    assert f"\\22size\\22: {2 * 2 * rows * channels * 2 + 8 * 2**20}}}]" in forward.as_text()
    assert _kernel_calls(forward.compile()) == 1
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2))).lower(*args)
    assert f"\\22size\\22: {2 * 3 * rows * channels * 2 + 8 * 2**20}}}]" in grad.as_text()
    compiled = grad.compile()
    assert _kernel_calls(compiled) == 2
    for kernel in ("ssm_conv_fwd", "ssm_conv_bwd"):
        assert len(re.findall(rf"^\s*%\w*{kernel}[\w.]* = .*custom-call\(", compiled.as_text(), re.M)) == 1, kernel


@pytest.mark.parametrize("batch, seq, heads", [(1, 4096, 32), (2, 128, 6)],
                         ids=["ling3flash-32-heads-8-a-step", "6-heads-a-step-two-chunks"])
def test_kda_kernels_compile_within_the_scoped_vmem_they_ask_for(as_tpu, batch, seq, heads):
    """`kda_fwd` and `kda_bwd` at the `train-ling3flash-4k` cell's shapes (a
    grid step: one chunk of 64 positions of 8 heads of 128, their (8, 128, 128)
    float32 states in scratch) and at another block of heads: the rule says
    "pallas" there, a differentiated rule is the two calls, each named, and
    Mosaic fits both in the `_VMEM_LIMIT` their calls ask for (a kernel that
    did not would be refused here as on the chip)."""
    from ray_tpu.ops import kda

    plan = kda.kda_plan(64, heads=heads, d_k=128, d_v=128)
    per_step = min(heads, 8)
    assert (plan["kda_impl"], plan["kda_kernels"], plan["kda_heads_per_step"], plan["kda_state_bytes"]) == (
        "pallas", 2, per_step, per_step * 128 * 128 * 4)
    flat = (batch, seq, heads * 128)
    # the mixer's own arguments: q, k, v and the gate's input flat, the two logits a head [beta | gate], `A_log` a
    # head, the bias a channel, the norm's scale a head's features
    args = (_on(as_tpu, flat), _on(as_tpu, flat), _on(as_tpu, flat), _on(as_tpu, flat),
            _on(as_tpu, (batch, seq, 2 * heads), jnp.float32), _on(as_tpu, (heads,), jnp.float32),
            _on(as_tpu, (heads * 128,), jnp.float32), _on(as_tpu, (128,), jnp.float32))
    rule = lambda *a: kda.kda_rule(*a, eps=1e-6, norm_eps=1e-6)[0]       # noqa: E731
    forward = jax.jit(rule).lower(*args)
    # the scoped VMEM the call asks Mosaic for, as the lowered call carries it
    assert f"\\22size\\22: {kda._VMEM_LIMIT}}}]" in forward.as_text()
    assert _kernel_calls(forward.compile()) == 1
    compiled = jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a).astype(jnp.float32) ** 2),
                                argnums=tuple(range(8)))).lower(*args).compile()
    assert _kernel_calls(compiled) == 2
    for kernel in ("kda_fwd", "kda_bwd"):
        assert len(re.findall(rf"^\s*%\w*{kernel}[\w.]* = .*custom-call\(", compiled.as_text(), re.M)) == 1, kernel


def test_the_lowered_mixer_holds_no_float32_copy_of_the_group_view(as_tpu):
    """One Mamba-2 mixer of the `train-nemotron3nano-8k` cell, forward and
    gradient, compiled for the described v5e: the convolution is the two
    kernels under `ssm.conv` and the gate and the group norm the two under
    `ssm.gate_norm`, and NO operation of the program
    copies, reshapes or transposes a float32 array of the product's size
    (2 x 8,192 x 4,096 x 4 bytes: the group view's shuffles, five a layer
    before PR 50). The in-projection's output reaches the kernels as it is
    written (no slice of z or of xBC in front of them, no copy of the
    projection or of the convolution's output), and the gate's and xBC's
    cotangents join dt's with no pad of their own to the projection's width."""
    from benchmark import model_config
    from ray_tpu.models import mixed_stack, model_family

    config = model_config.transformer_config(model_config.load_config(
        os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "nemotron-3-nano-30b-a3b-train-1chip.json")))
    shapes = jax.eval_shape(lambda: model_family(config).init_params(config, jax.random.PRNGKey(0)))
    one = SingleDeviceSharding(as_tpu)
    lp = jax.tree.map(lambda w: jax.ShapeDtypeStruct(w.shape[1:], w.dtype, sharding=one), shapes["runs"][0][0])

    def loss(x, lp):
        return jnp.sum(mixed_stack._ssm_sublayer(x, lp, config)[0].astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(_on(as_tpu, (2, 8192, config.d_model)), lp).compile()
    text = compiled.as_text()
    assert _kernel_calls(compiled) == 6 and "%ssm_gate_norm_fwd" in text and "%ssm_gate_norm_bwd" in text
    assert "%ssm_conv_fwd" in text and "%ssm_conv_bwd" in text
    product = 2 * 8192 * 4096
    moved = [line.strip()[:160] for line in text.splitlines()
             for found in [re.match(r"\s*(?:ROOT )?%[\w.\-]+ = f32\[([\d,]+)\]\S* (copy|reshape|transpose)\(", line)]
             if found and math.prod(int(d) for d in found.group(1).split(",")) >= product]
    assert not moved, moved
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"= bf16\[2,8192,4096\]\S* (slice|copy)\(%fusion", entry)     # z is not cut out of the projection
    assert not re.search(r"= bf16\[2,8192,(6144|10304)\]\S* (slice|copy)\(", entry)     # nor xBC, nor either copied
    assert not re.search(r"= bf16\[(2,8192|16384),10304\]\S* pad\(", entry)        # nor its cotangent padded to the width


def test_eva_attention_kernels_compile_at_the_evabyte_cells_shard(as_tpu):
    """train-evabyte-fsdp4-32k, one chip's sequence: 1 x 32 x 32,768 x 128,
    window 2,048, chunk 16. The forward is the causal flash kernel over 512
    windows-as-heads and `eva_far_fwd`; differentiated, also `flash_bwd_dkv_dq`
    with the merged output and lse and `eva_far_bwd` (a head's 2,048 summaries
    resident, their float32 cotangents summed over the 16 windows in place)."""
    from ray_tpu.ops import eva

    qkv = _on(as_tpu, (1, 32, 32768, 128))
    vector = _on(as_tpu, (32, 128), jnp.float32)

    def attend(q, k, v, mu, phi):
        return eva.eva_attention(q, k, v, mu, phi, window=2048, chunk=16)

    forward = jax.jit(attend).lower(qkv, qkv, qkv, vector, vector).compile()
    assert _kernel_calls(forward) == 2
    grad = jax.jit(jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))).lower(
        qkv, qkv, qkv, vector, vector).compile()
    assert _kernel_calls(grad) == 4
    for kernel in ("flash_fwd", "flash_bwd_dkv_dq", "eva_far_fwd", "eva_far_bwd"):
        assert len(re.findall(rf"^\s*%{kernel}[.\d]* = .*custom-call\(", grad.as_text(), re.M)) == 1, kernel
    assert eva.eva_plan(32768, window=2048, chunk=16, head_dim=128)["eva_impl"] == "pallas"
