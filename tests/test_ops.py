"""Kernel correctness: Pallas flash attention (interpret mode) vs XLA reference."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    apply_rope,
    cross_entropy_loss,
    flash_attention,
    layernorm,
    mha_reference,
    rmsnorm,
    rope_frequencies,
)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
def test_flash_forward_matches_reference(causal, gqa):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, s, d = 2, 4, 256, 64
    hkv = 2 if gqa else hq
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))
    ref = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=causal))(q, k, v)
    out = flash_attention(q, k, v, causal=causal, implementation="pallas",
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_forward_unpadded_vs_padded():
    # seq not a multiple of the block: wrapper pads + masks
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 192, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))
    ref = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=False))(q, k, v)
    out = flash_attention(q, k, v, causal=False, implementation="pallas",
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, d = 1, 2, 256, 64
    q = _rand(kq, (b, h, s, d))
    k = _rand(kk, (b, h, s, d))
    v = _rand(kv, (b, h, s, d))

    def loss_pallas(q, k, v):
        o = flash_attention(q, k, v, causal=causal, implementation="pallas")
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * o)

    gp = jax.jit(jax.grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3, rtol=1e-3)


def test_flash_backward_gqa():
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    b, hq, hkv, s, d = 1, 4, 2, 128, 32
    q = _rand(kq, (b, hq, s, d))
    k = _rand(kk, (b, hkv, s, d))
    v = _rand(kv, (b, hkv, s, d))

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, implementation="pallas") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gp = jax.jit(jax.grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3, rtol=1e-3)


# ------------------------------------------- sub-tile walk of a resident block
#
# At the default blocks the kernels hold one (min(S, 1024))^2 grid tile a
# head and walk it in sub-tiles, visiting only those with a live pair.


_WALK_CASES = {
    # name: (S, D, Hq, Hkv, causal)
    "s1024-d64-causal": (1024, 64, 1, 1, True),
    "s1024-d64-full": (1024, 64, 1, 1, False),
    "s1024-d128-causal": (1024, 128, 1, 1, True),
    "s1024-d128-full": (1024, 128, 1, 1, False),
    # padded to 2 x 2 grid tiles of 1024: the kv_len edge on a sub-tile border
    "s1536-causal-padded": (1536, 64, 1, 1, True),
    # ... and inside a sub-tile, every q sub-block reaching it
    "s1400-full-padded": (1400, 64, 1, 1, False),
    # a grid tile above, on and below the diagonal
    "s2048-causal-grid2x2": (2048, 64, 1, 1, True),
    "s1024-gqa4to1-causal": (1024, 64, 4, 1, True),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_flash_subtile_walk_matches_reference(case):
    s, d, hq, hkv, causal = _WALK_CASES[case]
    kq, kk, kv, ko = jax.random.split(jax.random.PRNGKey(30), 4)
    q = _rand(kq, (1, hq, s, d))
    k = _rand(kk, (1, hkv, s, d))
    v = _rand(kv, (1, hkv, s, d))
    w = _rand(ko, (1, hq, s, d))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, implementation="pallas")
    ref = lambda q, k, v: mha_reference(q, k, v, causal=causal)  # noqa: E731
    np.testing.assert_allclose(np.asarray(jax.jit(kernel)(q, k, v)),
                               np.asarray(jax.jit(ref)(q, k, v)), atol=2e-5, rtol=2e-5)
    gk = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


_CAUSAL_GRID_CASES = {
    # name: (S, Hq, Hkv, block): 4 x 4 tiles of 512 (10 live), walked in 256s
    "s2048-b512-gqa4to2": (2048, 4, 2, 512),
    "s2048-b512-mha2": (2048, 2, 2, 512),
    # no tile divides it: padded to 2,048, the last tile's rows past 1,900 sliced off
    "s1900-b512-padded": (1900, 2, 1, 512),
    # an odd number of tiles a side
    "s1536-b512-3x3": (1536, 2, 2, 512),
}


@pytest.mark.parametrize("case", sorted(_CAUSAL_GRID_CASES))
def test_causal_grid_matches_reference_and_dense_grid(monkeypatch, case):
    """The grid of live tiles (`_live_grid`: causal, square tiles, more than
    one a head) against `mha_reference` and against the values of the dense
    grid it replaced, forward and all three gradients."""
    from ray_tpu.ops import attention as A

    s, hq, hkv, block = _CAUSAL_GRID_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(37), 4)
    q, w = (_rand(key, (1, hq, s, 32)) for key in (keys[0], keys[3]))
    k, v = (_rand(key, (1, hkv, s, 32)) for key in keys[1:3])

    def both(attend):
        # functions of their own a call: jit keys its cache on the function, and
        # the second call of `kernels` has to be traced under the patched grid
        loss = lambda q, k, v: jnp.sum(attend(q, k, v) * w)  # noqa: E731
        out = jax.jit(lambda q, k, v: attend(q, k, v))(q, k, v)
        forward_alone.append(set(names))
        return (out, *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    kernels = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, implementation="pallas", block_q=block, block_kv=block)
    names, forward_alone = [], []
    call = A.pl.pallas_call
    monkeypatch.setattr(A.pl, "pallas_call", lambda *a, **kw: (
        names.append((kw["name"], "grid_spec" in kw)), call(*a, **kw))[1])
    live = both(kernels)
    assert set(names) == {("flash_fwd", True), ("flash_bwd_dkv_dq", True)}
    names.clear()
    monkeypatch.setattr(A, "_live_grid", lambda *a: False)
    dense = both(kernels)
    assert set(names) == {("flash_fwd", False), ("flash_bwd_dkv_dq", False)}
    assert forward_alone == [{("flash_fwd", True)}, {("flash_fwd", False)}]
    reference = both(lambda q, k, v: mha_reference(q, k, v, causal=True))
    for got, was, want, tol in zip(live, dense, reference, (2e-5, 1e-3, 1e-3, 1e-3)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)
        np.testing.assert_allclose(np.asarray(got), np.asarray(was), atol=2e-5, rtol=2e-5)
    # the forward sums in the dense grid's order: its values are the parent's,
    # to what the interpreter's own fusions round
    np.testing.assert_allclose(np.asarray(live[0]), np.asarray(dense[0]), atol=1e-6, rtol=0)


# The one backward kernel on each grid it serves: (b, hq, hkv, s, d, causal, window, kv_len)
_BACKWARD_CASES = {
    "one-tile-s1024-d64": (1, 2, 2, 1024, 64, True, None, None),
    "causal-grid-s4096": (1, 1, 1, 4096, 32, True, None, None),
    "band-window-2048-of-8192": (1, 1, 1, 8192, 32, True, 2048, None),
    "grouped-32-over-4": (1, 32, 4, 2048, 32, True, None, None),
    "wide-head-d256": (1, 1, 1, 2048, 256, True, None, None),
    "dense-full-grid2x2": (1, 2, 1, 2048, 32, False, None, None),
    "one-tile-kv-len-700": (1, 1, 1, 1024, 64, False, None, 700),
    "dense-grid-kv-len-700": (1, 1, 1, 2048, 32, False, None, 700),
}


@pytest.mark.parametrize("case", sorted(_BACKWARD_CASES))
def test_one_backward_kernel_matches_reference(monkeypatch, case):
    """`flash_bwd_dkv_dq` (a windowed call's `flash_win_bwd_dkv_dq`): ONE
    `pallas_call` a call of attention gives dQ, dK and dV, a head's dQ summed
    over kv tiles in a float32 scratch of the whole q sequence, against the
    gradients of `mha_reference`: one tile a head, the causal grid of live
    tiles, a band, grouped queries, a head of 256, a dense grid, and a `kv_len`
    edge inside a tile and between the tiles of a dense grid."""
    from ray_tpu.ops import attention as A

    b, hq, hkv, s, d, causal, window, kv_len = _BACKWARD_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(45), 4)
    q, do = (_rand(key, (b, hq, s, d)) for key in (keys[0], keys[3]))
    k, v = (_rand(key, (b, hkv, s, d)) for key in keys[1:3])
    names = []
    call = A.pl.pallas_call
    monkeypatch.setattr(A.pl, "pallas_call", lambda *a, **kw: (names.append(kw["name"]), call(*a, **kw))[1])
    scale = d ** -0.5
    if kv_len is None:
        def ours(q, k, v):
            return flash_attention(q, k, v, causal=causal, window=window, implementation="pallas")
        got = jax.jit(lambda q, k, v, do: jax.vjp(ours, q, k, v)[1](do))(q, k, v, do)
    else:
        block = min(s, 1024)
        out, lse = A._fwd_pallas(q, k, v, causal, scale, block, block, kv_len, True)
        got = A._bwd_pallas(q, k, v, out, lse, do, causal, scale, block, block, kv_len, True)
    backward = "flash_win_bwd_dkv_dq" if window else "flash_bwd_dkv_dq"
    assert sorted(names) == sorted(["flash_win_fwd" if window else "flash_fwd", backward])
    want = jax.jit(lambda q, k, v, do: jax.vjp(lambda q, k, v: mha_reference(
        q, k, v, causal=causal, window=window, kv_len=kv_len), q, k, v)[1](do))(q, k, v, do)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5 * float(jnp.max(jnp.abs(r))),
                                   err_msg=f"d{name}")


def test_a_head_whose_dq_does_not_fit_is_refused_by_name():
    """The backward keeps a head's float32 dQ in VMEM beside a tile's blocks:
    64 k tokens at D = 256 are past the chip's 128 MiB, and say so."""
    from ray_tpu.ops.attention import _resident_dq_bytes, attention_plan

    assert _resident_dq_bytes(32768, 256) == 32 * 1024 * 1024
    assert _resident_dq_bytes(131072, 128) == 64 * 1024 * 1024
    for seq, head_dim in ((65536, 256), (262144, 128)):
        with pytest.raises(ValueError, match="dQ in VMEM"):
            _resident_dq_bytes(seq, head_dim)
        with pytest.raises(ValueError, match="dQ in VMEM"):
            attention_plan(seq, implementation="pallas", head_dim=head_dim)


def test_flash_rows_no_subtile_reaches():
    """kv_len = 0 visits nothing: o = 0 and lse = -inf, the contract ring
    attention's merge relies on. Padded query rows (beyond kv_len) see
    every real key and stay finite."""
    from ray_tpu.ops.attention import _NEG_INF, _bwd_pallas, _fwd_pallas

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(31), 3)
    q = _rand(kq, (1, 1, 1024, 64))
    k = _rand(kk, (1, 1, 1024, 64))
    v = _rand(kv, (1, 1, 1024, 64))
    out, lse = _fwd_pallas(q, k, v, False, 0.125, 1024, 1024, 0, True)
    assert not np.asarray(out).any()
    np.testing.assert_array_equal(np.asarray(lse), np.float32(_NEG_INF))
    grads = _bwd_pallas(q, k, v, out, lse, q, False, 0.125, 1024, 1024, 0, True)
    assert not any(np.asarray(g).any() for g in grads)
    # causal, kv_len 700 of 1024: the edge inside the third 256-wide sub-tile
    out, lse = _fwd_pallas(q, k, v, True, 0.125, 1024, 1024, 700, True)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(lse)).all() and (np.asarray(lse) > -1e29).all()
    ref = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=True, sm_scale=0.125, kv_len=700))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "args,expect",
    [((1024, 1024, True, 1024, 1024, 1024, 256, 256), (10, 4, 16)),
     ((1024, 1024, True, 1024, 1024, 1024, 128, 128), (36, 8, 64)),
     ((1024, 1024, True, 1024, 1024, 1024, 512, 512), (3, 2, 4)),
     ((1024, 1024, False, 1024, 1024, 1024, 256, 256), (16, 0, 16)),
     # a causal grid of tiles a head, each its own sub-tile: the one below the
     # diagonal unmasked
     ((2048, 2048, True, 2048, 1024, 1024, 1024, 1024), (3, 2, 4)),
     # one tile, kv_len 700: 3 of 4 kv sub-tiles a strip, the third crossed
     ((1024, 1024, False, 700, 1024, 1024, 256, 256), (12, 4, 16)),
     # rectangular sub-tiles
     ((1024, 1024, True, 1024, 1024, 1024, 512, 256), (6, 4, 8)),
     # explicit small blocks: a grid of single sub-tiles
     ((256, 256, True, 256, 128, 64, 128, 64), (6, 6, 8)),
     # a causal grid: below the diagonal 16 of 16 unmasked a tile, the diagonal
     # tile walked as one tile a head is (10, 4 of them masked) ...
     ((16384, 16384, True, 16384, 1024, 1024, 256, 256), (2080, 64, 4096)),
     ((8192, 8192, True, 8192, 1024, 1024, 256, 256), (528, 32, 1024)),
     ((4096, 4096, True, 4096, 1024, 1024, 256, 256), (136, 16, 256)),
     # ... whatever kv_len says (causality implies it on every real row)
     ((2048, 2048, True, 1536, 1024, 1024, 256, 256), (36, 8, 64)),
     ((2048, 2048, True, 2048, 512, 512, 256, 256), (36, 8, 64)),
     # ... but by the forward, which computes it whole and masked
     ((16384, 16384, True, 16384, 1024, 1024, 256, 256, None, "flash_fwd"), (2176, 256, 4096)),
     ((4096, 4096, True, 4096, 1024, 1024, 256, 256, None, "flash_bwd_dkv_dq"), (136, 16, 256)),
     # not causal, or not square: the dense grid, every live tile masked
     ((2048, 2048, False, 2048, 1024, 1024, 1024, 1024), (4, 4, 4)),
     ((2048, 2048, True, 2048, 1024, 512, 1024, 512), (6, 6, 8)),
     # a band of 5 at window 4,096 (16 diagonal + 42 interior + 12 trailing tiles
     # a head): the backward kernel walks the edge tiles (10 of 16, 4 masked) ...
     ((16384, 16384, True, 16384, 1024, 1024, 256, 256, 4096, "flash_bwd_dkv_dq"),
      (28 * 10 + 42 * 16, 28 * 4, 4096)),
     # ... and is the kernel that is counted where none is named
     ((16384, 16384, True, 16384, 1024, 1024, 256, 256, 4096), (952, 112, 4096)),
     # ... the forward computes them whole: 16 visited and 16 masked a tile
     ((16384, 16384, True, 16384, 1024, 1024, 256, 256, 4096, "flash_fwd"),
      (70 * 16, 28 * 16, 4096)),
     # a band of 3 at window 2,048: 8 + 7 + 6
     ((8192, 8192, True, 8192, 1024, 1024, 256, 256, 2048, "flash_fwd"), (21 * 16, 14 * 16, 1024)),
     ((8192, 8192, True, 8192, 1024, 1024, 256, 256, 2048, "flash_bwd_dkv_dq"),
      (14 * 10 + 7 * 16, 14 * 4, 1024))],
    ids=["causal-256", "causal-128", "causal-512", "full-256", "grid2x2",
         "kv-edge", "rectangular", "small-blocks", "causal-grid-16k", "causal-grid-8k",
         "causal-grid-4k", "causal-grid-padded", "causal-grid-block512", "causal-grid-16k-forward",
         "causal-grid-4k-backward", "full-grid2x2", "causal-rectangular-grid", "band5-16k-backward",
         "band5-16k-default", "band5-16k-forward", "band3-8k-forward", "band3-8k-backward"],
)
def test_attention_subtiles_counts(args, expect):
    from ray_tpu.ops.attention import attention_subtiles

    assert attention_subtiles(*args) == expect


@pytest.mark.parametrize(
    "args,expect",
    [((16384, 16384, True, 16384, 1024, 1024), (136, 136)),
     ((4096, 4096, True, 4000, 1024, 1024), (10, 10)),
     ((1024, 1024, True, 1024, 1024, 1024), (1, 1)),
     # the dense grid steps through its dead tiles: above the diagonal, beyond kv_len
     ((4096, 4096, True, 4096, 1024, 512), (32, 20)),
     ((2048, 2048, False, 1000, 1024, 1024), (4, 2)),
     ((2048, 2048, False, 2048, 1024, 1024), (4, 4)),
     # a band: a q tile's first steps fall before the sequence
     ((8192, 8192, True, 8192, 1024, 1024, 2048), (24, 21))],
    ids=["causal-grid-16k", "causal-grid-padded", "one-tile", "causal-rectangular",
         "full-kv-edge", "full", "band"],
)
def test_attention_grid_steps(args, expect):
    """The steps a head's grid has and those of them that hold a live pair:
    equal for a causal grid, whose steps are the lower triangle's tiles."""
    from ray_tpu.ops.attention import attention_grid_steps

    assert attention_grid_steps(*args) == expect


def test_attention_plan_names_what_runs(monkeypatch):
    """What LMTrainer writes on `train.init.step_fn` and chip_smoke.py
    prints: both cells train causal at S = 1,024."""
    from ray_tpu.ops.attention import attention_plan

    assert attention_plan(1024) == {
        "attention_impl": "xla", "attn_subtiles_visited": 0,
        "attn_subtiles_masked": 0, "attn_subtiles_total": 0,
        "attn_grid_steps": 0, "attn_grid_steps_live": 0, "attn_bwd_kernels": 0}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention_plan(1024) == {
        "attention_impl": "pallas", "attn_subtiles_visited": 10,
        "attn_subtiles_masked": 4, "attn_subtiles_total": 16,
        "attn_grid_steps": 1, "attn_grid_steps_live": 1, "attn_bwd_kernels": 1}
    # the one backward kernel keeps a head's float32 dQ in VMEM: 256 KiB at the
    # two one-tile cells' gpt2 heads, 8 MiB at SmallThinker's and at GLM's
    assert attention_plan(1024, head_dim=64)["attn_bwd_resident_bytes"] == 256 * 1024
    assert (attention_plan(16384, head_dim=128)["attn_bwd_resident_bytes"]
            == attention_plan(8192, head_dim=256)["attn_bwd_resident_bytes"] == 8 * 1024 * 1024)
    assert attention_plan(3000, head_dim=128)["attn_bwd_resident_bytes"] == 3072 * 128 * 4
    assert attention_plan(1024, causal=False)["attn_subtiles_visited"] == 16
    assert attention_plan(1024, implementation="xla")["attention_impl"] == "xla"
    # a causal grid of tiles (the three cells past one tile a head): the live
    # tiles alone are steps, counted in the walk's sub-tiles
    for seq, visited, masked, steps in [(2048, 36, 8, 3), (4096, 136, 16, 10),
                                        (8192, 528, 32, 36), (16384, 2080, 64, 136)]:
        plan = attention_plan(seq)
        assert (plan["attn_subtiles_visited"], plan["attn_subtiles_masked"],
                plan["attn_subtiles_total"]) == (visited, masked, (seq // 256) ** 2)
        assert plan["attn_grid_steps"] == plan["attn_grid_steps_live"] == steps
    # not causal: the dense grid, every tile live and masked
    assert attention_plan(2048, causal=False)["attn_subtiles_masked"] == 4
    banded = attention_plan(8192, window=2048)
    assert (banded["attn_grid_steps"], banded["attn_grid_steps_live"]) == (24, 21)
    # the band's tiles by class: the forward computes all 21 (and all 70 at
    # SmallThinker's shape) whole; the sub-tiles are the backward kernel's, whose
    # edge tiles are walked (10 of 16, 4 masked) and whose 7 (42) interior tiles are whole
    assert banded["attn_window_tiles_whole"] == 21
    assert (banded["attn_subtiles_visited"], banded["attn_subtiles_masked"]) == (252, 56)
    wider = attention_plan(16384, window=4096)
    assert (wider["attn_grid_steps_live"], wider["attn_window_tiles_whole"]) == (70, 70)
    assert (wider["attn_subtiles_visited"], wider["attn_subtiles_masked"]) == (952, 112)
    assert "attn_window_tiles_whole" not in attention_plan(8192)


@pytest.mark.parametrize("s,causal", [(1024, True), (1024, False), (700, True),
                                      (2048, True), (3000, True), (2048, False)],
                         ids=["causal", "full", "odd-block", "grid2x2", "grid3x3-padded",
                              "full-grid2x2"])
def test_kernels_walk_the_counted_subtiles(monkeypatch, s, causal):
    """What the two kernels (the forward, and the one backward kernel that
    builds a tile's scores once for dK, dV and dQ) compute while they are
    traced is what `attention_subtiles` counts: every QK^T piece is recorded
    with its size in sub-tiles and whether it builds the mask."""
    from ray_tpu.ops import attention as A

    pieces = []
    scores = A._scores

    def recording(q, k, scale, mask_at, *rest):
        pieces.append((q.shape[0] * k.shape[0], mask_at is not None))
        return scores(q, k, scale, mask_at, *rest)

    monkeypatch.setattr(A, "_scores", recording)
    q = jnp.zeros((1, 1, s, 64), jnp.float32)
    jax.grad(lambda q: flash_attention(
        q, q, q, causal=causal, implementation="pallas").sum())(q)
    block = min(1024, s)
    padded = s + (-s) % block
    side = padded // block
    live_grid = A._live_grid(causal, None, block, block, side, side)
    sub_q, sub_kv = A._sub_tiles(block, block, 1 if live_grid else side ** 2)
    area = sub_q * sub_kv
    counts = {kernel: A.attention_subtiles(padded, padded, causal, s, block, block, sub_q,
                                           sub_kv, kernel=kernel)
              for kernel in ("flash_fwd", "flash_bwd_dkv_dq")}
    if side == 1:
        # static walk: each kernel's pieces add up to the count
        (visited, masked, total), = set(counts.values())
        assert sum(n for n, _ in pieces) == 2 * visited * area
        assert sum(n for n, m in pieces if m) == 2 * masked * area
        assert total == (padded // sub_q) * (padded // sub_kv)
    elif live_grid:
        # a causal grid: a kernel holds one body a class, run per grid step.
        # Below the diagonal one whole unmasked piece; the diagonal tile's
        # pieces are the count's, a tile
        below, whole = side * (side - 1) // 2, (block // sub_q) * (block // sub_kv)
        assert [p for p in pieces if not p[1] and p[0] == block * block] == [(block * block, False)] * 2
        on_diagonal = sum(n for n, _ in pieces) - 2 * block * block
        assert on_diagonal * side == sum(v - below * whole for v, _, _ in counts.values()) * area
        assert sum(n for n, m in pieces if m) * side == sum(m for _, m, _ in counts.values()) * area
        assert counts["flash_fwd"][:2] != counts["flash_bwd_dkv_dq"][:2]
        plan_steps = A.attention_grid_steps(padded, padded, causal, s, block, block)
        assert plan_steps == (side * (side + 1) // 2,) * 2
    else:
        # a dense grid of tiles: one masked whole-tile body a kernel, run or
        # not per grid step
        assert pieces == [(block * block, True)] * 2
        assert set(counts.values()) == {(4, 4, 4)}


def _tiles_by_runs(runs_of, strips, key):
    """{(a, c, is_masked)} of the sub-tiles the strips' runs reach."""
    return {key(strip, index, masked) for strip in range(strips)
            for lo, hi, masked in runs_of(strip) for index in range(lo, hi)}


def _assert_ranges_cover(live_pair, sub, causal, kv_len, window=None):
    from ray_tpu.ops.attention import _kv_runs, _q_runs

    sub_q, sub_kv = sub
    size = live_pair.shape[0]
    nq, nk = size // sub_q, size // sub_kv
    by_rows = _tiles_by_runs(
        lambda a: _kv_runs(a * sub_q, 0, nk, sub_q, sub_kv, causal, kv_len, window),
        nq, lambda a, c, m: (a, c, m))
    by_cols = _tiles_by_runs(
        lambda c: _q_runs(0, c * sub_kv, nq, sub_q, sub_kv, causal, kv_len, window),
        nk, lambda c, a, m: (a, c, m))
    assert by_rows == by_cols
    # every live pair is in a visited sub-tile, every masked pair in a masked one
    for a in range(nq):
        for c in range(nk):
            tile = live_pair[a * sub_q:(a + 1) * sub_q, c * sub_kv:(c + 1) * sub_kv]
            kind = {m for (a_, c_, m) in by_rows if (a_, c_) == (a, c)}
            assert bool(tile.any()) == bool(kind)
            if kind:
                assert kind == {not tile.all()}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_len", [1024, 700, 0])
@pytest.mark.parametrize("sub", [(256, 256), (128, 256), (512, 128)])
def test_subtile_ranges_agree(causal, kv_len, sub):
    """`_q_range` (the backward kernel's bounds) reaches exactly the set
    `_kv_range` (forward, the counter) reaches, with the same masked
    sub-tiles, and that set is the live pairs'."""
    rows, cols = np.arange(1024)[:, None], np.arange(1024)[None, :]
    live_pair = (cols < kv_len) & ((cols <= rows) | (not causal))
    _assert_ranges_cover(live_pair, sub, causal, kv_len)


@pytest.mark.parametrize("window", [1, 100, 256, 300, 512, 1024, 4096])
@pytest.mark.parametrize("sub", [(256, 256), (128, 256), (512, 128)])
def test_subtile_ranges_agree_under_a_window(window, sub):
    """The same with the window's lower edge (key j live iff i - window < j
    <= i; no kv_len edge, causality implies it)."""
    rows, cols = np.arange(1024)[:, None], np.arange(1024)[None, :]
    _assert_ranges_cover((cols <= rows) & (cols > rows - window), sub, True, None, window)


# Bands with interior tiles, at the chip's geometry a sixteenth the size (tiles
# of 64 in 4 x 4 sub-tiles of 16): (s, window, block) -> the band's classes
_BANDS_WITH_INTERIOR_TILES = {
    "band3-window-2-tiles": ((320, 128, 64), ["diagonal", "interior", "trailing"]),
    "band4-window-3-tiles": ((448, 192, 64), ["diagonal", "interior", "interior", "trailing"]),
    # the window's edge inside a tile: two trailing tiles, the walk visits 3 of 16 of the last
    "band5-edge-inside-a-tile": ((512, 210, 64), ["diagonal", "interior", "interior", "trailing", None]),
    "band5-window-4-tiles": ((384, 256, 64), ["diagonal", "interior", "interior", "interior", "trailing"]),
    # every q tile's band starts before the sequence; no tile divides the length
    "band-longer-than-the-sequence": ((200, 256, 64), ["diagonal", "interior", "interior", "interior"]),
    # narrower than a tile: the walk visits 7 and 1 of 16, so neither tile is a class
    "window-under-a-tile": ((256, 16, 64), [None, None]),
}


@pytest.mark.parametrize("s,window,block,classes", [
    *((*case, "table") for case in [
        (48, 64, None), (64, 64, None), (96, 32, 32), (128, 48, 32), (100, 32, 32),
        (128, 32, 64), (128, 200, 32), (64, 16, None), (128, 1, 32)]),
    *((*case, classes) for case, _ in _BANDS_WITH_INTERIOR_TILES.values()
      for classes in ("table", "walked", "whole"))],
    ids=["below-window", "at-window", "above-3x3", "edge-inside-a-tile", "no-tile-divides",
         "two-tiles", "window-over-the-sequence", "one-tile-walked", "window-of-one",
         *(f"{name}-{classes}" for name in _BANDS_WITH_INTERIOR_TILES
           for classes in ("table", "walked", "whole"))])
def test_windowed_kernels_match_the_masked_reference(monkeypatch, s, window, block, classes):
    """`flash_attention(window=)` in interpret mode against `mha_reference`
    with the same mask, forward and all three gradients, GQA: sequences below,
    at and above the window, a window edge inside a tile, and a length no tile
    divides; bands of 3 to 5 tiles with interior ones, by `_BAND_WALK` as it
    stands and with every class walked (16 x 16 sub-tiles here) and every
    class whole in both kernels."""
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_SUB_TILE", (16, 16))
    if classes != "table":
        monkeypatch.setattr(A, "_BAND_WALK", {kernel: dict.fromkeys(by_class, classes == "walked")
                                              for kernel, by_class in A._BAND_WALK.items()})
    keys = jax.random.split(jax.random.PRNGKey(s + window), 4)
    q, do = (_rand(key, (1, 4, s, 32)) for key in (keys[0], keys[3]))
    k, v = (_rand(key, (1, 2, s, 32)) for key in keys[1:3])

    def objective(implementation):
        return lambda q, k, v: jnp.sum(do * flash_attention(
            q, k, v, causal=True, window=window, implementation=implementation,
            block_q=block, block_kv=block))

    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, implementation="pallas", block_q=block, block_kv=block))(q, k, v)
    want = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=True, window=window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the reference's window is the stated one: key j live iff i - window < j <= i
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = np.einsum("hqd,hkd->hqk", np.asarray(q[0]), np.repeat(np.asarray(k[0]), 2, 0)) / 32 ** 0.5
    scores = np.where((cols <= rows) & (cols > rows - window), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    by_hand = np.einsum("hqk,hkd->hqd", probs / probs.sum(-1, keepdims=True),
                        np.repeat(np.asarray(v[0]), 2, 0))
    np.testing.assert_allclose(np.asarray(want[0]), by_hand, atol=2e-5)
    for ours, theirs in zip(jax.jit(jax.grad(objective("pallas"), (0, 1, 2)))(q, k, v),
                            jax.jit(jax.grad(objective("xla"), (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=5e-5)


def test_band_tiles_have_the_class_their_offset_says(monkeypatch):
    """`_band_class` from the offset, the side and the window alone, against
    the pairs themselves: interior iff every pair of the tile is live."""
    from ray_tpu.ops import attention as A

    monkeypatch.setattr(A, "_SUB_TILE", (16, 16))
    for (s, window, block), want in _BANDS_WITH_INTERIOR_TILES.values():
        band = A.window_band(window, block, -(-s // block))
        assert [A._band_class(d, band, block, window) for d in range(band)] == want
        for d in range(band):
            rows, cols = d * block + np.arange(block)[:, None], np.arange(block)[None, :]
            live = (cols <= rows) & (cols > rows - window)
            assert live.any() and live.all() == (want[d] == "interior")
    # the two cells: 3 of 5 and 1 of 3 band tiles interior; one tile a head is no class
    monkeypatch.undo()
    assert [A._band_class(d, 5, 1024, 4096) for d in range(5)] == [
        "diagonal", "interior", "interior", "interior", "trailing"]
    assert [A._band_class(d, 3, 1024, 2048) for d in range(3)] == ["diagonal", "interior", "trailing"]
    assert A._band_class(0, 1, 1024, 2048) is None


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkv_dq"])
@pytest.mark.parametrize("s,window,block", [
    (8192, 2048, 1024),     # a band of 3 tiles a q tile: 21 of the 36 causal tiles
    (16384, 4096, 1024),    # a band of 5: 70 tiles a head, 42 of them interior
    (1024, 2048, 1024),     # one tile: the causal walk, 10 / 4 / 16
    (2048, 256, 1024), (4096, 1000, 512), (4096, 1500, 1024),
    (8192, 4096, 1024),     # a band of 5 that most q tiles start before the sequence
    (4096, 3000, 1024),     # the window's edge inside the band's last tile
    (3072, 1024, 512)])     # a band of 3 at half the tile side
def test_windowed_subtile_counts(s, window, block, kernel):
    """`attention_subtiles` under a window, a kernel, against a count over the
    pairs themselves: in a tile the kernel walks a sub-tile is visited iff it
    holds a live pair and masked iff it also holds a dead one; a tile it
    computes whole (`_BAND_WALK` by `_band_class`) visits all 16 and masks all
    or, with every pair live, none."""
    from ray_tpu.ops import attention as A

    tiles, subs = s // block, block // 256
    band = A.window_band(window, block, tiles)
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    live = ((cols <= rows) & (cols > rows - window)).reshape(tiles, subs, 256, tiles, subs, 256)
    some, every = live.any(axis=(2, 5)), live.all(axis=(2, 5))    # (tile, sub, tile, sub)
    visited = masked = whole_tiles = 0
    for i in range(tiles):
        for d in range(min(band, i + 1)):
            tile_some, tile_every = some[i, :, i - d], every[i, :, i - d]
            if A._band_whole(kernel, d, band, block, window):
                whole_tiles += 1
                visited += subs * subs
                masked += 0 if tile_every.all() else subs * subs
            else:
                visited += int(tile_some.sum())
                masked += int((tile_some & ~tile_every).sum())
    assert A.attention_subtiles(s, s, True, s, block, block, 256, 256, window, kernel) == (
        visited, masked, (s // 256) ** 2)
    if kernel == "flash_fwd":
        plan_whole = sum(tiles - d for d in range(band) if A._band_whole(kernel, d, band, block, window))
        assert plan_whole == whole_tiles


@pytest.mark.parametrize("s,window", [(4096, 2048), (6144, 4096), (4096, 1500), (2048, 256)],
                         ids=["band3", "band5", "two-trailing-tiles", "under-a-tile"])
def test_windowed_kernels_trace_the_counted_pieces(monkeypatch, s, window):
    """What the two windowed kernels hold, as they are traced at the chip's
    tile and sub-tile: ONE body for the interior tiles, a whole unmasked
    piece, and one for each other band tile, whose pieces are what
    `attention_subtiles` counts for that tile under that kernel."""
    from ray_tpu.ops import attention as A

    pieces = []
    scores = A._scores

    def recording(q, k, scale, mask_at, *rest):
        pieces.append((q.shape[0] * k.shape[0], mask_at is not None))
        return scores(q, k, scale, mask_at, *rest)

    monkeypatch.setattr(A, "_scores", recording)
    q = jnp.zeros((1, 1, s, 64), jnp.float32)
    jax.eval_shape(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True, window=window, implementation="pallas").sum()), q)
    band, area = A.window_band(window, 1024, s // 1024), 256 * 256
    live = masked = 0
    for kernel in ("flash_fwd", "flash_bwd_dkv_dq"):
        bodies = {1 if A._band_class(d, band, 1024, window) == "interior" else d for d in range(band)}
        for d in bodies:
            on_visited, on_masked = A._band_tile_subtiles(kernel, d, band, 1024, 256, 256, window)
            live, masked = live + on_visited * area, masked + on_masked * area
    assert sum(n for n, _ in pieces) == live
    assert sum(n for n, m in pieces if m) == masked
    interior = [d for d in range(band) if A._band_class(d, band, 1024, window) == "interior"]
    assert pieces.count((1024 * 1024, False)) == (2 if interior else 0)


def test_window_needs_causal_self_attention():
    q = jnp.zeros((1, 1, 64, 32))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=16)
    with pytest.raises(ValueError, match="square"):
        flash_attention(q, q, q, causal=True, window=16, block_q=32, block_kv=64,
                        implementation="pallas")


def test_rmsnorm_and_layernorm():
    x = _rand(jax.random.PRNGKey(4), (2, 8, 64))
    scale = jnp.ones((64,))
    bias = jnp.zeros((64,))
    out = rmsnorm(x, scale)
    expected = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)
    ln = layernorm(x, scale, bias)
    np.testing.assert_allclose(np.asarray(ln).mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ln).std(-1), 1.0, atol=1e-3)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = _rand(jax.random.PRNGKey(5), (1, 2, 16, 64))
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(x[:, :, 0]), atol=1e-6)


def test_rope_with_positions():
    cos, sin = rope_frequencies(32, 64)
    x = _rand(jax.random.PRNGKey(6), (2, 1, 4, 32))
    pos = jnp.array([[3, 4, 5, 6], [0, 1, 2, 3]])
    out = apply_rope(x, cos, sin, positions=pos)
    # batch 1 with offset positions == default arange
    default = apply_rope(x[1:2], cos, sin)
    np.testing.assert_allclose(np.asarray(out[1:2]), np.asarray(default), atol=1e-6)


def test_cross_entropy_against_manual():
    logits = _rand(jax.random.PRNGKey(7), (4, 16))
    targets = jnp.array([1, 5, 2, 9])
    loss, n = cross_entropy_loss(logits, targets)
    logp = jax.nn.log_softmax(np.asarray(logits, dtype=np.float32), axis=-1)
    expected = -np.mean([logp[i, t] for i, t in enumerate(np.asarray(targets))])
    np.testing.assert_allclose(float(loss), expected, rtol=1e-6)
    assert float(n) == 4.0


def test_cross_entropy_masked():
    logits = _rand(jax.random.PRNGKey(8), (2, 4, 16))
    targets = jnp.zeros((2, 4), dtype=jnp.int32)
    mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]])
    loss, n = cross_entropy_loss(logits, targets, mask=mask)
    assert float(n) == 3.0
    assert np.isfinite(float(loss))


def test_cross_entropy_z_loss_increases_loss():
    logits = 5.0 * _rand(jax.random.PRNGKey(9), (4, 16))
    targets = jnp.array([0, 1, 2, 3])
    base, _ = cross_entropy_loss(logits, targets)
    with_z, _ = cross_entropy_loss(logits, targets, z_loss_coeff=1e-2)
    assert float(with_z) > float(base)


def test_flash_kernel_runs_per_shard_under_a_context_mesh():
    """Traced under a context mesh (as make_train_step does) the Pallas
    kernel is shard_mapped — batch over dp x fsdp, heads over tp — because
    GSPMD cannot partition a Mosaic call on a real chip. Same values and
    gradients as the unsharded reference, GQA included."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(26), 3)
    q = _rand(kq, (4, 4, 64, 32))
    k = _rand(kk, (4, 2, 64, 32))
    v = _rand(kv, (4, 2, 64, 32))
    spec = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), "tp", None, None))

    def loss_sharded(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            out = flash_attention(q, k, v, causal=True, implementation="pallas",
                                  block_q=32, block_kv=32)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    step = jax.jit(jax.value_and_grad(loss_sharded, argnums=(0, 1, 2)),
                   in_shardings=(spec, spec, spec))
    assert "manual_computation" in step.lower(q, k, v).as_text()
    val, grads = step(q, k, v)
    ref_val, ref_grads = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-4)
    for a, b_ in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


V5E_HBM = int(15.75 * 2 ** 30)
# gpt2-small as `train/lm.head_and_remat_for` counts it: 124.4 M float32 parameters with AdamW's
# two moments; their gradients and, a row, 12 layers x 18 x 768 bfloat16 activations
GPT2S_STATE, GPT2S_GRADIENTS, GPT2S_ROW = 1.493e9, 0.498e9, 12 * 18 * 768 * 2


@pytest.mark.parametrize("batch,seq,hbm,want", [
    # the whole sequence as one chunk: 149,395 tokens/s at 80.2% of a v5e against 149,090 (512),
    # 147,923 (256) and the dense head's 143,573 at 94.5% (chip, PERF.md section 6, PR 46)
    (24, 1024, V5E_HBM, 1024),
    # two chunks: 148,833 at 87.9% against 148,226 (256); the whole sequence (estimate 17.8 GB
    # of 16.9) ran at the compiler's ceiling, 94.8%, and lost 5.6% (same runs)
    (32, 1024, V5E_HBM, 512),
    # a sequence that few candidates divide: itself, 256, 128; none fits, so the smallest
    (32, 1280, V5E_HBM, 128),
    (8, 1280, V5E_HBM, 1280),
    # a device of unknown size (the CPU) keeps the dense head
    (1024, 1024, None, 0),
    (24, 1024, 0, 0),
], ids=["gpt2s-24-whole-sequence", "gpt2s-32-two-chunks", "gpt2s-32x1280-smallest", "gpt2s-8x1280-whole",
        "unknown-size-dense", "size-0-dense"])
def test_auto_loss_chunk_crossover(batch, seq, hbm, want):
    """The fused head's chunk at the measured v5e points: no dense head
    where the device's size is known, the whole sequence where the head's
    moment has room for it, the next candidate that divides S where not."""
    from ray_tpu.ops.losses import auto_loss_chunk

    assert auto_loss_chunk(batch, seq, 50257, hbm, resident_bytes=int(GPT2S_STATE),
                           step_bytes=int(GPT2S_GRADIENTS + batch * seq * GPT2S_ROW)) == want


def test_check_kernel_fallbacks_wired():
    """scripts/check_kernel_fallbacks.py is now a shim over the raylint
    kernel-fallbacks rule; the repo-wide gate runs ONCE in
    tests/test_raylint.py. Here: the round-6 knobs stay registered and
    the shim's compat API resolves cfg reads."""
    import ast
    import importlib.util
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    script = repo / "scripts" / "check_kernel_fallbacks.py"
    spec = importlib.util.spec_from_file_location("ckf", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    config_tree = ast.parse(
        (repo / "ray_tpu" / "core" / "config.py").read_text()
    )
    flags = mod.defined_flags(config_tree)
    assert set(mod.REQUIRED_FLAGS) <= flags
    reads = mod.cfg_reads(ast.parse(
        "from .config import cfg\nx = cfg.serve_ragged_kernel\n"
    ))
    assert reads == [(2, "serve_ragged_kernel")]


def test_fused_linear_cross_entropy_matches_dense():
    """The chunked fused head+CE must agree with the
    dense path — values AND gradients — including mask and z-loss."""
    from ray_tpu.ops.losses import fused_linear_cross_entropy

    key = jax.random.PRNGKey(11)
    b, s, e, v, chunk = 2, 8, 16, 32, 4
    x = _rand(key, (b, s, e))
    head = _rand(jax.random.PRNGKey(12), (e, v))
    targets = jax.random.randint(jax.random.PRNGKey(13), (b, s), 0, v)
    mask = jnp.array([[1] * 8, [1, 1, 1, 1, 0, 0, 0, 0]])

    def dense(x, head):
        logits = jnp.einsum("bse,ev->bsv", x, head)
        return cross_entropy_loss(
            logits, targets, mask=mask, z_loss_coeff=1e-3
        )[0]

    def fused(x, head):
        return fused_linear_cross_entropy(
            x, head, targets, chunk=chunk, mask=mask, z_loss_coeff=1e-3
        )[0]

    np.testing.assert_allclose(
        float(dense(x, head)), float(fused(x, head)), rtol=1e-5
    )
    gd = jax.jit(jax.grad(dense, argnums=(0, 1)))(x, head)
    gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(x, head)
    for a, b_ in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)

    with pytest.raises(ValueError):
        fused_linear_cross_entropy(x, head, targets, chunk=5)


def _head_case(dtype=jnp.float32):
    b, s, e, v = 2, 64, 8, 50
    x = _rand(jax.random.PRNGKey(21), (b, s, e), dtype)
    head = (0.3 * _rand(jax.random.PRNGKey(22), (e, v))).astype(dtype)
    targets = jax.random.randint(jax.random.PRNGKey(23), (b, s), 0, v)
    mask = (jax.random.uniform(jax.random.PRNGKey(24), (b, s)) > 0.3).astype(jnp.int32)
    return x, head, targets, mask


@pytest.mark.parametrize(
    "case", ["plain", "mask", "z_loss", "mask_z_loss", "cotangent", "tied", "jit", "bf16",
             "whole_sequence", "whole_sequence_mask_z_loss", "whole_sequence_bf16",
             "module_mask", "whole_sequence_module_mask"])
def test_chunked_head_value_and_gradients_match_the_dense_loss(case):
    """`fused_linear_cross_entropy` computes dx and dW in the chunk's
    forward pass (one custom_vjp); the dense `cross_entropy_loss` under
    plain autodiff is the reference for the value and both gradients. Also
    where the rule sends it since PR 46: the whole sequence as ONE chunk (a
    scan of one step), and the multi-token prediction module's pass
    (`train/lm.lm_loss`: the targets rolled by one, the last position, which
    has no such target, masked out)."""
    from ray_tpu.ops.losses import fused_linear_cross_entropy

    dtype = jnp.bfloat16 if "bf16" in case else jnp.float32
    x, head, targets, mask = _head_case(dtype)
    if "module_mask" in case:
        targets = jnp.roll(targets, -1, axis=1)
        mask = jnp.broadcast_to(jnp.arange(targets.shape[1]) < targets.shape[1] - 1, targets.shape)
    chunk = x.shape[1] if "whole_sequence" in case else 16
    kw = {"mask": mask if "mask" in case else None,
          "z_loss_coeff": 1e-2 if "z_loss" in case else 0.0}
    scale = 3.0 if case == "cotangent" else 1.0
    # a tied head reaches the loss as the transpose of the embedding
    w = head.T if case == "tied" else head
    weights = (lambda w: w.T) if case == "tied" else (lambda w: w)

    def dense(x, w):
        logits = jnp.einsum("bse,ev->bsv", x, weights(w))
        return scale * cross_entropy_loss(logits, targets, **kw)[0]

    def chunked(x, w):
        loss, num = fused_linear_cross_entropy(x, weights(w), targets, chunk=chunk, **kw)
        return scale * loss, num

    want, (want_dx, want_dw) = jax.jit(jax.value_and_grad(dense, argnums=(0, 1)))(x, w)
    grad = jax.value_and_grad(chunked, argnums=(0, 1), has_aux=True)
    (got, num), (got_dx, got_dw) = (jax.jit(grad) if case == "jit" else grad)(x, w)
    assert got_dx.dtype == x.dtype and got_dw.dtype == w.dtype and got_dw.shape == w.shape
    assert float(num) == (float(mask.sum()) if "mask" in case else targets.size)
    # float32: the sums run in another order; bfloat16: dx and dW leave the
    # dense path through the same casts, so one ulp (2^-8) of the largest entry
    tol = {"rtol": 2e-2, "atol": 2e-3} if "bf16" in case else {"rtol": 1e-5, "atol": 1e-6}
    np.testing.assert_allclose(float(got), float(want), rtol=1e-2 if "bf16" in case else 1e-5)
    np.testing.assert_allclose(np.asarray(got_dx, np.float32), np.asarray(want_dx, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(got_dw, np.float32), np.asarray(want_dw, np.float32), **tol)
    # the undifferentiated call (evaluation) is the same loss
    np.testing.assert_allclose(float(chunked(x, w)[0]), float(got), rtol=1e-6)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _scan_bodies(jaxpr):
    return [eqn.params["jaxpr"].jaxpr for eqn in _equations(jaxpr) if eqn.primitive.name == "scan"]


def _count(jaxpr, primitive):
    return sum(eqn.primitive.name == primitive for eqn in _equations(jaxpr))


def test_chunked_head_builds_each_chunks_logits_once():
    """Three matmuls a chunk under differentiation (logits, dx, dW), one
    without; nothing is rematerialized, and nothing the backward keeps has
    a vocabulary-sized axis beside more than a chunk of rows."""
    from ray_tpu.ops import losses

    x, head, targets, mask = _head_case()
    chunk, vocab = 16, head.shape[1]

    def loss(x, head):
        return losses.fused_linear_cross_entropy(
            x, head, targets, chunk=chunk, mask=mask, z_loss_coeff=1e-3)[0]

    grad = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, head).jaxpr
    (body,) = _scan_bodies(grad)
    assert _count(body, "dot_general") == 3
    assert _count(grad, "dot_general") == 3   # none outside the scan: the backward only scales
    names = {eqn.primitive.name for eqn in _equations(grad)}
    assert not names & {"checkpoint", "remat", "remat2"}, names

    plain = jax.make_jaxpr(loss)(x, head).jaxpr
    (body,) = _scan_bodies(plain)
    assert _count(body, "dot_general") == 1 and _count(plain, "dot_general") == 1

    _, residuals = jax.eval_shape(
        lambda *a: losses._chunked_cross_entropy_fwd(*a, chunk, 1e-3),
        x, head, targets, mask.astype(jnp.float32))
    shapes = sorted(r.shape for r in jax.tree.leaves(residuals))
    assert shapes == sorted([x.shape, head.shape])
    for shape in shapes:
        if vocab in shape:
            assert math.prod(shape) // vocab <= chunk, shape


def test_chunked_head_under_the_grad_accum_scan_of_the_train_step():
    """`make_train_step(grad_accum=2)` differentiates the loss inside a
    scan over microbatches: the chunked head there gives the dense head's
    loss and update."""
    import optax
    from ray_tpu.models import get_config
    from ray_tpu.parallel import single_device_mesh
    from ray_tpu.train import create_train_state, make_train_step

    config = get_config("gpt2-tiny")
    mesh = single_device_mesh()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, config.vocab_size)

    def run(loss_chunk):
        opt = optax.sgd(0.1)   # the update is the gradient: no Adam sign step near zero
        state, sh = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
        step = make_train_step(config, opt, mesh, state_shardings=sh, grad_accum=2,
                               loss_chunk=loss_chunk, z_loss_coeff=1e-3)
        state, metrics = step(state, {"tokens": tokens})
        return state, metrics

    dense_state, dense = run(0)
    chunked_state, chunked = run(16)
    np.testing.assert_allclose(float(chunked["loss"]), float(dense["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(chunked["grad_norm"]), float(dense["grad_norm"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(dense_state.params), jax.tree.leaves(chunked_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4)
